#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

Usage, from the root of a checkout on a machine with the chips the cell
asks for::

    python benchmarks/chip/run_cell.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``configs/<name>.json`` here) under a traffic (``traffic/<name>.json``).
The window is a closed loop of jobs, each one architect's
``Experiment(...).run(workers=1)`` from a workload spec to scored rows;
jobs start until ``--seconds`` have passed and the job in flight finishes.
A ``sharded`` traffic scores a ``ShardedSpec`` from a shard store that
set-up builds under a temporary directory and the run removes at its end.
``accesses_per_s`` is the simulated accesses of every job over the time
to the last job's end, with the device queue drained at each timestamp.
With ``--trace 1`` the window also records the program's stage spans and
a profiler trace, and the metrics are the cell's per-layer metrics, each
read by ``metrics/<name>.py``.

After the window a sample of jobs drawn from the seed is compared with the
plain reference (``check.py``), which also regenerates every prefetch
stream the sample scored; a cell naming a prefetcher that has no plain
generator is refused at load.  The compared numbers and their limits are
the last lines of standard error and the last key of the result line,
which is the last line of standard output.  Without a TPU, or with fewer
chips than the cell asks for, the run exits non-zero before any job.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import check  # noqa: E402
import xplane  # noqa: E402
from layers import Layers  # noqa: E402
from reference.cache import SetGroups, default_workers  # noqa: E402
from reference.prefetchers import GENERATORS, can_generate  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------ the benchmark


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path  # the benchmark's directory

    def reader(self, metric: str) -> Callable:
        spec = importlib.util.spec_from_file_location(
            f"metric_{metric.replace('.', '_').replace('-', '_')}",
            self.root / "metrics" / f"{metric}.py",
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(checkout: Path, workload: str) -> Cell:
    """Find a workload's configuration, traffic and metrics by name."""
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    root = checkout / bench["paths"][0]
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(entries)}")
    w = entries[workload]
    (cfg,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    cell = Cell(
        name=workload,
        chips=int(w["chips"]),
        config=json.loads((checkout / cfg["file"]).read_text()),
        traffic=json.loads((root / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        root=root,
    )
    entries = [
        p
        for index in range(len(cell.traffic["jobs"]))
        for p in Traffic.chosen(cell.config, cell.traffic, index)
    ]
    if cell.traffic["workload"] == "sharded":
        derived = {p["name"] for p in entries} & SHARDED_DERIVED
        if derived:
            raise BadCell(
                f"{workload}: the sharded path derives the stream of "
                f"{sorted(derived)} itself and never issues it through the "
                "prefetcher, so the check could not re-score it; leave it out "
                "of a sharded traffic"
            )
    plainless = sorted({p["name"] for p in entries if not can_generate(p)})
    if plainless:
        known = "; ".join(f"{k} with {sorted(g.overrides)}" for k, g in GENERATORS.items())
        raise BadCell(
            f"{workload}: {plainless} name a registry entry or an override that "
            "has no plain generator in reference/prefetchers.py, so the check "
            f"could not regenerate their streams (there are: {known})"
        )
    return cell


class BadCell(ValueError):
    """A cell whose traffic the harness cannot run and check."""


# Prefetchers whose stream the sharded scorer derives from the demand stream
# by name (``core/exec/sharded.py``), never calling their generator.
SHARDED_DERIVED = {"nextline2"}


# ----------------------------------------------------------------- jobs


def job_seed(seed: int, index: int) -> int:
    """A 32-bit workload seed for job ``index`` of a run with ``seed``."""
    return int(np.random.SeedSequence([seed, index]).generate_state(1)[0])


@dataclasses.dataclass
class Job:
    index: int
    accesses: int
    result: object  # the ExperimentResult
    streams: Dict[tuple, tuple]  # (job's spec, prefetcher) -> stream


class Traffic:
    """The one job generator: a traffic file's parameters over a config.

    ``workload``: ``per_job`` builds each job's workload fresh from a seed
    derived from (run seed, job index); ``per_run`` builds one workload in
    set-up and every job scores against it; ``sharded`` builds the shard
    store of one ``ShardedSpec`` (``shard_accesses`` to a shard) in set-up,
    under a temporary directory that :meth:`close` removes, and every job
    scores it through the sharded streaming path.  ``jobs`` is cycled in
    order; each entry names its prefetchers (``"config"`` for the
    configuration's own list).
    """

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.sharded = traffic["workload"] == "sharded"
        self.cache = None
        self.store_dir: Optional[str] = None
        self.manifest: Optional[dict] = None
        self._trace: Optional[tuple] = None  # the store's (block, iter_id)

    def hierarchy(self):
        from repro.memsim.config import CacheLevelConfig, HierarchyConfig

        h = self.config["hierarchy"]
        level = lambda k: CacheLevelConfig(  # noqa: E731
            h[k]["size_bytes"], h[k]["ways"], h[k]["latency"], h[k]["mshr"]
        )
        return HierarchyConfig(
            l1=level("l1"),
            l2=level("l2"),
            llc=level("llc"),
            dram_latency=h["dram_latency"],
            pf_fill_window=h["pf_fill_window"],
            name=h["name"],
        )

    def spec(self, index: int):
        from repro.core import WorkloadSpec

        per_job = self.traffic["workload"] == "per_job"
        spec = WorkloadSpec(
            self.config["kernel"],
            self.config["dataset"],
            hierarchy=self.hierarchy(),
            seed=job_seed(self.seed, index if per_job else 0),
        )
        if self.sharded:
            from repro.core.exec.sharded import ShardedSpec

            spec = ShardedSpec(spec, int(self.traffic["shard_accesses"]))
        return spec

    def setup(self) -> None:
        """Make the graph; with ``per_run``, build the shared workload; with
        ``sharded``, build the shard store."""
        from repro.apps import kernel_traits
        from repro.core import WorkloadCache
        from repro.graphs import make_dataset

        make_dataset(
            self.config["dataset"], weighted=kernel_traits(self.config["kernel"]).weighted
        )
        if self.traffic["workload"] == "per_run":
            self.cache = WorkloadCache()
            self.cache.get_or_build(self.spec(0))
        if self.sharded:
            from repro.core.exec.artifacts import ArtifactCache
            from repro.core.exec.sharded import ensure_shards

            self.store_dir = tempfile.mkdtemp(prefix="bench-shards-")
            self.cache = WorkloadCache(artifacts=ArtifactCache(self.store_dir))
            self.manifest = ensure_shards(self.spec(0), self.cache.artifacts)

    def close(self) -> None:
        """Remove the shard store."""
        if self.store_dir is not None:
            shutil.rmtree(self.store_dir, ignore_errors=True)
            self.store_dir = None

    @staticmethod
    def chosen(config: dict, traffic: dict, index: int) -> list:
        """The prefetcher entries job ``index`` scores."""
        entry = traffic["jobs"][index % len(traffic["jobs"])]
        chosen = entry["prefetchers"]
        return config["prefetchers"] if chosen == "config" else chosen

    def prefetchers(self, index: int, streams: dict, spec) -> list:
        from repro.core.registry import get_prefetcher

        out = []
        for p in self.chosen(self.config, self.traffic, index):
            generate = get_prefetcher(p["registry"]).instantiate(**p["overrides"])
            out.append((p["name"], _recording(generate, (spec, p["name"]), streams)))
        return out

    def run(self, index: int) -> Job:
        from repro.core import Experiment

        streams: dict = {}
        spec = self.spec(index)
        result = Experiment(
            workloads=[spec],
            prefetchers=self.prefetchers(index, streams, spec),
            cache=self.cache,
        ).run(workers=1)
        if self.sharded:
            accesses = int(self.manifest["num_accesses"])
        else:
            accesses = sum(w.num_accesses for w in result.workloads.values())
        return Job(index, accesses, result, streams)

    def answers(self, job: Job) -> dict:
        """A finished job's answers in the shape ``check.compare`` reads:
        each row with the stream its prefetcher issued and its entry
        (``registry``, ``overrides``), for the check to regenerate."""
        if self.sharded:
            return {"workloads": [self._sharded_answers(job)]}
        out = []
        for spec, w in job.result.workloads.items():
            p = w.profile
            rows = [self._row(job, spec, c) for c in job.result.cells if c.spec == spec]
            out.append(
                dict(
                    seed=spec.seed,
                    block=w.block,
                    iter_id=w.iter_id,
                    eval_from=w.eval_from_pos,
                    l1_hit=p.l1_hit,
                    l2_hit=p.l2_hit,
                    llc_hit=p.llc_hit,
                    rows=rows,
                )
            )
        return {"workloads": out}

    def _sharded_answers(self, job: Job) -> dict:
        """The trace is the store's shards, as the timed path read them; the
        rows carry the demand counts in place of per-access masks."""
        spec = self.spec(job.index)
        if self._trace is None:
            store = self.cache.artifacts
            shards = [
                store.load_shard(spec, k)
                for k in range(len(self.manifest["shard_sizes"]))
            ]
            self._trace = tuple(
                np.concatenate([s[key] for s in shards]) for key in ("block", "iter_id")
            )
        rows = [self._row(job, spec, c) for c in job.result.cells]
        return dict(
            seed=spec.seed,
            block=self._trace[0],
            iter_id=self._trace[1],
            eval_from=self.manifest["eval_from_pos"],
            rows=rows,
        )

    def _row(self, job: Job, spec, cell) -> tuple:
        """A scored row, the stream its prefetcher issued, and its entry."""
        (entry,) = [
            p for p in self.chosen(self.config, self.traffic, job.index)
            if p["name"] == cell.prefetcher
        ]
        return cell.metrics.row(), job.streams[(spec, cell.prefetcher)], entry


def _recording(generate: Callable, key: tuple, streams: dict) -> Callable:
    """``generate``, keeping each stream it issues for the check under
    ``key``: the job's spec and the prefetcher's name.  The workload it is
    given may be a plain trace or the sharded path's view of one."""

    def recorded(workload):
        stream = generate(workload)
        streams[key] = (stream.blocks, stream.pos, stream.metadata_bytes)
        return stream

    return recorded


# ----------------------------------------------------------------- clocks


def clock() -> float:
    """Wall time after the device queue has drained.

    Work is issued in order on the chip, so blocking on a fresh trivial
    computation waits for everything issued before it.
    """
    import jax
    import jax.numpy as jnp

    jax.block_until_ready(jnp.zeros((), jnp.int32) + 1)
    return time.perf_counter()


class Compiles:
    """Counts JAX's compiles and persistent-cache hits by phase."""

    def __init__(self):
        import jax

        self.phase = "setup"
        self.counts: Dict[str, Dict[str, float]] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _count(self, key: str, seconds: float = 0.0) -> None:
        c = self.counts.setdefault(self.phase, {})
        c[key] = c.get(key, 0) + 1
        if seconds:
            c[key + "_s"] = c.get(key + "_s", 0.0) + seconds

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if "backend_compile" in event:
            self._count("compiles", seconds)

    def _on_event(self, event: str, **_) -> None:
        if "compilation_cache/cache_hits" in event:
            self._count("cache_hits")

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


@dataclasses.dataclass
class Window:
    jobs: int  # jobs completed
    accesses: int  # their simulated accesses
    sample: list  # the jobs kept for the check, in window order
    failed: int
    job_s: list  # each job's seconds, drained
    t0: float  # the window's start, drained, on ``clock``
    t1: float  # the end of its last job, drained
    wall0: int  # ``time.time_ns()`` at ``t0``, the spans' clock

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def window(
    run_job: Callable, first: int, seconds: float, clock: Callable, log,
    keep: int = 1, rng: Optional[np.random.Generator] = None,
    annotate: Callable = contextlib.nullcontext,
) -> Window:
    """Start jobs ``first, first + 1, ...`` one after another until
    ``seconds`` have passed; the job in flight then finishes and counts.

    ``keep`` jobs, drawn uniformly by ``rng`` (reservoir sampling), are
    kept for the check; every other job is dropped as it finishes, so the
    window holds no more than the program itself would.  ``annotate``
    opens the profiler's window mark at the first timestamp.
    """
    rng = rng if rng is not None else np.random.default_rng(0)
    done, accesses, sample, failed, index, job_s = 0, 0, [], 0, first, []
    t0 = t1 = clock()
    wall0 = time.time_ns()
    with annotate():
        while t1 - t0 < seconds:
            try:
                job = run_job(index)
            except Exception as e:  # a job that fails counts, and fails the run
                failed += 1
                log(f"[bench] job {index} failed: {type(e).__name__}: {e}")
            else:
                accesses += job.accesses
                if done < keep:
                    sample.append(job)
                else:
                    slot = int(rng.integers(0, done + 1))
                    if slot < keep:
                        sample[slot] = job
                done += 1
                del job
            index += 1
            start, t1 = t1, clock()
            job_s.append(t1 - start)
    sample.sort(key=lambda j: j.index)
    return Window(done, accesses, sample, failed, job_s, t0, t1, wall0)


def chips_or_fail(need: int):
    """The devices, or :class:`NoChip` without enough accelerators."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < need:
        raise NoChip(
            f"JAX found {len(devices)} {devices[0].platform} device(s); "
            f"this cell needs {need} accelerator chip(s)"
        )
    return devices


# ----------------------------------------------------------------- a run


def run(
    checkout: Path,
    workload: str,
    seed: int,
    seconds: float,
    traced: bool,
    require_chip: bool = True,
    control: bool = False,
    keep_trace: Optional[str] = None,
    log: Callable[[str], None] = lambda s: print(s, file=sys.stderr, flush=True),
) -> dict:
    """One run of a cell; returns the result line as a dict.

    ``control`` also reads the correctness control on the same sample: the
    reference under FIFO replacement put in the program's place.  Whatever
    the traffic stored is removed when the run ends, by failure too.
    """
    cell = load_cell(checkout, workload)
    import jax

    devices = chips_or_fail(cell.chips) if require_chip else jax.devices()
    traffic = Traffic(cell.config, cell.traffic, seed)
    try:
        return _measure(cell, traffic, devices, seed, seconds, traced,
                        require_chip, control, keep_trace, log)
    finally:
        traffic.close()


def _measure(cell, traffic, devices, seed, seconds, traced, require_chip, control,
             keep_trace, log) -> dict:
    """Set-up, the window and the check of :func:`run`."""
    import jax

    compiles = Compiles()
    from repro.core.exec.compile_cache import use_compile_cache
    from repro.core.obs import spans as obs

    cache_dir = use_compile_cache()
    traffic.setup()
    warm = traffic.run(0)  # compiles this cell's shapes
    del warm
    gc.collect()

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        jax.profiler.start_trace(trace_dir)
    first = 1 if cell.traffic["workload"] == "per_job" else 0
    rss_at_window = _rss_bytes()
    cache_before = _tree_bytes(cache_dir)
    clock()  # compiles the drain op outside the window
    with obs.trace() if traced else contextlib.nullcontext() as tracer:
        compiles.phase = "window"
        w = window(
            traffic.run, first, seconds, clock, log,
            keep=int(cell.traffic["check_jobs"]),
            rng=np.random.default_rng([seed, 0xC4EC]),
            annotate=(lambda: jax.profiler.TraceAnnotation(xplane.WINDOW))
            if traced else contextlib.nullcontext,
        )
        compiles.phase = "after"
    compiles.close()
    setup_s = w.t0 - PROCESS_START
    failed, window_s, accesses = w.failed, w.seconds, w.accesses
    if traced:
        jax.profiler.stop_trace()

    stats = [d.memory_stats() or {} for d in devices[: cell.chips]]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": cell.chips if require_chip else len(devices),
        "memory_peak_bytes": int(peak),
    }
    print("[bench] device " + json.dumps(device), flush=True)
    print(
        "[bench] memory "
        + json.dumps(
            {
                "device_peak_bytes": int(peak),
                "host_peak_rss_bytes": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                * 1024,
                "host_wchar_bytes": _proc_bytes("io", "wchar:"),
            }
        ),
        flush=True,
    )
    print(
        "[bench] window "
        + json.dumps(
            {
                "jobs": w.jobs,
                "failed": failed,
                "accesses": accesses,
                "seconds": window_s,
                "setup_s": setup_s,
                "job_s": w.job_s,
                "compiles": compiles.counts,
                "rss_bytes_at_window": rss_at_window,
                "compile_cache_bytes": [cache_before, _tree_bytes(cache_dir)],
                "shard_store_bytes": _tree_bytes(traffic.store_dir or ""),
            }
        ),
        flush=True,
    )

    metrics: Dict[str, dict] = {}
    breakdown = None
    if traced:
        layers = _layers(tracer, w.wall0, window_s, trace_dir)
        for m in cell.per_layer:
            value = cell.reader(m["name"])(layers)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if layers.device is not None:
            device["busy_s"] = layers.device.busy_s() or 0.0
            device["window_s"] = layers.device.window_s
            breakdown = _breakdown(layers)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane.find(trace_dir), keep_trace)
            with open(os.path.join(keep_trace, "spans.json"), "w") as f:
                json.dump(layers.spans, f)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = {"accesses_per_s": accesses / window_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # The check: the jobs the window kept, drawn from the seed.
    sample = [traffic.answers(j) for j in w.sample]
    w.sample = None
    gc.collect()
    with SetGroups(default_workers()) as groups:
        t_ref = time.perf_counter()
        reference = check.Reference(cell.config, groups=groups)
        numbers = check.compare(sample, reference)
        log(
            f"[bench] reference {time.perf_counter() - t_ref:.3f} s over "
            f"{len(sample)} job(s), {groups.workers} process(es)"
        )
        if control:
            t_ref = time.perf_counter()
            fifo = check.Reference(cell.config, policy="fifo", groups=groups)
            control_numbers = check.compare(sample, fifo, truth=reference)
            log(
                f"[bench] control {time.perf_counter() - t_ref:.3f} s "
                + json.dumps(control_numbers)
            )
    result = {
        "correct": failed == 0 and bool(sample) and check.verdict(numbers),
        "attempted": w.jobs + failed,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        result["control"] = control_numbers
    result["checks"] = {
        name: {"value": v, "limit": check.LIMITS[name]} for name, v in numbers.items()
    }
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return result


def _proc_bytes(name: str, key: str, unit: int = 1) -> int:
    """A count from ``/proc/self/<name>``, or 0 where it is not there."""
    try:
        with open(f"/proc/self/{name}") as f:
            for line in f:
                if line.startswith(key):
                    return int(line.split()[1]) * unit
    except OSError:
        pass
    return 0


def _rss_bytes() -> int:
    return _proc_bytes("status", "VmRSS:", 1024)


def _tree_bytes(path: str) -> int:
    total = 0
    for root, _, files in os.walk(path):
        total += sum(os.path.getsize(os.path.join(root, f)) for f in files)
    return total


def _layers(tracer, wall0: int, window_s: float, trace_dir: str) -> Layers:
    """The window's spans (seconds from its start) and device trace."""
    spans = []
    for s in tracer.spans:
        start = (s.ts - wall0) / 1e9
        if start < window_s and start + s.dur > 0:
            spans.append((s.name, start, start + s.dur))
    try:
        device = xplane.reduce(xplane.find(trace_dir))
    except RuntimeError as e:
        print(f"[bench] no device trace: {e}", file=sys.stderr)
        device = None
    return Layers(window_s=window_s, spans=spans, device=device)


def _breakdown(layers: Layers, top: int = 10) -> dict:
    """The device ops that took most time, and the longest idle gaps, each
    named after the innermost stage span open at its middle."""
    ops = sorted(layers.device.op_seconds().items(), key=lambda kv: -kv[1])[:top]
    w0 = layers.device.window[0]
    gaps = []
    for s, e in layers.device.idle_gaps():
        mid = ((s + e) / 2 - w0) / 1e9
        open_ = [(b - a, n) for n, a, b in layers.spans if a <= mid < b]
        gaps.append([min(open_)[1] if open_ else "harness", (e - s) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": gaps[:top]}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", help="copy the profiler trace into this directory")
    ap.add_argument(
        "--control",
        action="store_true",
        help="also read the correctness control on the same sample",
    )
    args = ap.parse_args(argv)
    # One fixed compile cache inside the checkout, taken by the program too;
    # every program is kept, so a second run of a cell compiles nothing.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    sys.path.insert(0, str(CHECKOUT / "src"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        result = run(
            CHECKOUT,
            args.workload,
            args.seed,
            args.seconds,
            bool(args.trace),
            control=args.control,
            keep_trace=args.keep_trace,
        )
    except NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
