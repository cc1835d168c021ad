"""Perf-trajectory benchmark harness for the experiment execution engine.

Times the pipeline stages (trace generation with the ``trace_emit``
sub-stage, demand simulation with per-level ``cache_pass[l1|l2|llc]``
breakdown, per-prefetcher scoring), the end-to-end evaluation grid —
serial with a cold workload-artifact cache, then at each ``--workers``
count against the warm cache — and a small 3-epoch evolving-graph stream
cell with the stream-protocol stage breakdown (``update_apply``,
``trace_epoch``, ``table_carry``) and its own serial-vs-parallel parity
gate, and emits a schema-stable ``BENCH_<date>.json`` at the repo root
(never clobbering an existing file: reruns on the same date get a ``.2``,
``.3``, ... infix so the trajectory keeps its before/after points).

Schema v4 adds the trace-emitter section: a full-workload rebuild under
the per-iteration *reference* emitter gated bit-identical against the
batched whole-run emitter, an emission micro-bench over representative
runs (including the long-horizon ``tinyroad`` traversal where the batched
pass wins hardest), and a ``bfs_do`` (direction-optimizing BFS) cell in
the full grid so pull-mode traces ride the whole pipeline.

Schema v5 adds the serving-subsystem section: K in {1, 4} concurrent
tenants (mixed kernels x seeds on ``tiny``) interleaved over one shared
LLC with both AMC table modes, reporting a queries/sec throughput cell
(K tenants / warm wall-clock at the fixed hierarchy), the serving stage
breakdown (``serve_interleave`` / ``serve_llc`` / ``serve_score``), and a
serial-vs-workers parity gate wired into the exit code like the
grid/stream gates.

Schema v6 adds the sharded paper-scale section: the ``ShardedSpec``
streaming-scoring path is parity-gated bit-identical against the unsharded
``score_prefetcher`` rows on a real cell, and (full mode) a peak-RSS gauge
scores the ~8.5M-edge ``road-8m`` cell and the ``comdblp`` cell in fresh
child interpreters at the same shard size, asserting the two peaks agree
within 10% — i.e. streaming memory is flat in trace length (32.5M vs 118k
accesses).  Both children run against the shared persistent XLA
compilation cache (warmed by one discarded run) so the gauge measures
streaming state, not one-time compile transients.

Schema v7 adds the scheduler section: the cost-aware ``workers=None``
default is run against the warm cache (its :class:`SchedDecision` record
is committed with the JSON, and a not-slower-than-``workers=1`` gate
keeps the auto path honest), and a cold A/B pits the cost-aware
pipelined schedule against the legacy phased ``workers=2`` schedule on
fresh artifact dirs — both parity-gated against serial.  The stream
section gains a zero-churn reuse cell exercising delta-aware epoch trace
reuse (content-keyed epochs: unchanged graphs are cache hits, counted by
``trace_reuse``) with a bit-identical reuse-vs-re-emission gate, plus the
``pipeline_overlap`` stage from the overlapped epoch handoff.

Schema v8 adds the telemetry section (``docs/OBSERVABILITY.md``): the
scheduler's auto warm run executes under a cross-process span tracer, and
the committed document carries the run manifest (git sha, resolved
engine/emitter, schema versions, SchedDecision), the merged metrics
registry snapshot (cache hit/build counters), and the merged span-trace summary covering parent and
worker processes.  ``tools/bench_diff.py`` gates CI on consecutive
documents; ``tools/trace_export.py`` renders traces for Perfetto.

Schema v9 adds the fused hierarchy-engine section: the default ``fused``
engine runs L1→L2→LLC demand simulation as ONE carried set-parallel scan
(per-access hit levels, no inter-level host round trips; a cost-based
plan chooser keeps short or run-light streams on the bit-identical
cascade) and batches the per-prefetcher scoring passes of one workload
into one vmapped launch per level, so the stage breakdown's
``cache_pass`` dict carries one ``fused`` key per fused-engine demand
walk (the always-zero ``score_cache_pass[l1]`` key is gone; only stages
that actually ran are emitted — ``tools/bench_diff.py`` aliases the
fused key to the sum of its per-level predecessors across the
transition).  The section runs a compile-warmed demand+score A/B of the
fused path against the per-level ``set_parallel`` cascade on the stage
cell — the committed ``speedup`` is the ratio of engine-attributable
seconds (the ``demand_sim`` stage plus the scoring ``cache_pass[*]``
stages; stream generation and the shared host-side outcome analysis are
engine-independent) — reports the wall times and fused launch counters
alongside, and gates the exit code on fused-vs-reference bit identity
(hit masks + scored rows, batched and looped).

The dated JSONs accumulate as the repo's machine-readable perf trajectory;
CI runs ``--smoke`` (1 kernel x 1 dataset x 3 prefetchers) on every push,
uploads the JSON as a build artifact, and fails this script (exit 1) when
the grid errors, parallel results diverge from serial, the set-parallel
cache engine diverges from the serial ``lax.scan`` reference, the batched
trace emitter diverges from the per-iteration reference, the sharded
streaming scorer diverges from the unsharded path, or (full mode) the
sharded peak-RSS gauge is not flat.

Usage:
    PYTHONPATH=src python -m benchmarks.bench [--smoke]
        [--kernels pgd,cc] [--datasets comdblp] [--prefetchers amc,vldp,rnr]
        [--workers 1,2,4] [--out-dir .] [--cache-dir DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import shutil
import sys
import tempfile
import time
from datetime import date
from functools import partial
from pathlib import Path

sys.path.insert(0, "src")

SCHEMA_VERSION = 9

# Three prefetchers spanning the suite's families: the paper's contribution
# (amc), a spatial baseline (vldp), and a replay baseline (rnr).  The
# per-prefetcher stage section and the CI smoke grid time all three; the
# full grid scores the two cheap ones so its cell cost stays dominated by
# trace construction, like a real sweep's.
PREFETCHERS = ["amc", "vldp", "rnr"]
GRID_PREFETCHERS = ["amc", "rnr"]
SMOKE_CELLS = [("pgd", "comdblp", 0)]
# The streaming-subsystem cell (schema v3): a 3-epoch sliding-window
# stream, timed for its own stages (update_apply / trace_epoch /
# table_carry) and parity-gated serial vs workers=2.
STREAM_EPOCHS = 3
STREAM_PREFETCHERS = ["amc", "nextline2"]
# The serving-subsystem cells (schema v5): K concurrent query tenants on
# the tiny dataset — mixed kernels and seeds so shared-table aliasing has
# cross-tenant material — timed cold and warm (queries/sec = K / warm
# seconds at the fixed SCALED hierarchy) and parity-gated serial vs
# workers=2.
SERVE_TENANT_COUNTS = [1, 4]
SERVE_TENANTS = [
    ("pgd", "tiny", 0),
    ("cc", "tiny", 0),
    ("pgd", "tiny", 1),
    ("cc", "tiny", 1),
]
SERVE_PREFETCHERS = ["amc", "nextline2"]
# (kernel, dataset, seed) cells on comdblp, both app protocols.  The
# seed-varied bfs/bellmanford cells are distinct evolving-graph trials
# (each seed draws a different §VI run1->run2 evolution), and their
# two-run builds dominate their cell cost — the proportions of a real
# sweep, where trace construction is the bulk of a cold grid.
FULL_CELLS = [
    ("pgd", "comdblp", 0),
    ("cc", "comdblp", 0),
    ("bfs", "comdblp", 0),
    ("bfs", "comdblp", 1),
    ("bfs", "comdblp", 2),
    ("bellmanford", "comdblp", 0),
    ("bellmanford", "comdblp", 1),
    ("bellmanford", "comdblp", 2),
    # Schema v4: direction-optimizing BFS — dense (pull) middle levels
    # emit the in-edge/source-gather pattern through the full pipeline.
    ("bfs_do", "comdblp", 0),
]
# Emission micro-bench runs (schema v4): kernel runs re-emitted under both
# emitters.  bfs/tinyroad is the long-horizon case (hundreds of small
# frontiers — per-iteration overhead dominates the reference emitter);
# pgd_pull/comdblp replays the dense body every iteration.
EMITTER_MICRO = [("bfs", "tinyroad"), ("pgd_pull", "comdblp")]
# Sharded paper-scale section (schema v6).  The parity sub-gate scores a
# real cell through the ShardedSpec streaming path at a shard size small
# enough to force many seams and compares rows bit-for-bit against the
# unsharded path.  The RSS gauge scores the two cells below — 275x apart
# in trace length — in fresh child interpreters at the same shard size and
# requires their ru_maxrss peaks to agree within SHARD_RSS_TOL.
SHARD_PREFETCHERS = ["amc", "nextline2"]
SHARD_PARITY_ACCESSES = 1 << 14
SHARD_GAUGE_ACCESSES = 1 << 16
SHARD_RSS_CELLS = [("bfs", "comdblp", 0), ("bfs", "road-8m", 0)]
SHARD_RSS_TOL = 0.10
# Scheduler section (schema v7).  The auto (workers=None) warm run must
# not lose to the pinned workers=1 reference beyond measurement noise,
# and the cost-aware cold schedule must not lose to the legacy phased
# workers=2 schedule it replaced (the BENCH_2026-08-07 inversion).
SCHED_AUTO_TOL = 1.10
SCHED_COLD_TOL = 1.05


def _sharded_child(argv) -> int:
    """Hidden ``--_score-sharded`` re-exec target for the peak-RSS gauge.

    Scores one pre-materialized sharded cell with the cheap ``nextline2``
    prefetcher in this (fresh) interpreter and reports its own peak RSS
    as JSON on stdout.  The child places the JAX persistent compilation
    cache by the same rule as the parent
    (:func:`repro.core.exec.compile_cache.use_compile_cache`), so a warmed
    cache makes the child's peak free of compile-time transients.

    The peak is read from ``/proc/self/status`` ``VmHWM``, which execve
    resets to this process's own image — ``getrusage``'s ``ru_maxrss``
    would instead inherit the high-water mark of the (large) parent bench
    process across fork/exec and report the parent's peak, not ours.
    """
    kernel, dataset, seed, shard_accesses, cache_dir = argv

    from repro.core import WorkloadSpec
    from repro.core.exec.artifacts import ArtifactCache
    from repro.core.exec.sharded import ShardedSpec, score_sharded
    from repro.core.registry import resolve_prefetchers

    spec = ShardedSpec(
        base=WorkloadSpec(kernel, dataset, seed=int(seed)),
        shard_accesses=int(shard_accesses),
    )
    cache = ArtifactCache(cache_dir)
    manifest = cache.load_manifest(spec)
    assert manifest is not None, "gauge cell must be pre-materialized"
    t0 = time.perf_counter()
    scored = score_sharded(spec, resolve_prefetchers(["nextline2"]), cache)
    dt = time.perf_counter() - t0

    def _peak_kb() -> int:
        try:
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith("VmHWM:"):
                        return int(line.split()[1])
        except OSError:
            pass
        import resource  # non-Linux fallback (fork-inheritance caveat)

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss

    json.dump(
        {
            "maxrss_kb": _peak_kb(),
            "score_s": round(dt, 2),
            "accesses": int(manifest["num_accesses"]),
            "shards": len(manifest["shard_sizes"]),
            "speedup": {n: round(m.speedup, 4) for n, m in scored},
        },
        sys.stdout,
    )
    print()
    return 0


def _gauge_child_run(kernel, dataset, seed, shard_accesses, cache_dir):
    """Run the hidden gauge mode in a fresh interpreter; parse its JSON."""
    import subprocess

    root = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [
            sys.executable,
            str(Path(__file__).resolve()),
            "--_score-sharded",
            kernel,
            dataset,
            str(seed),
            str(shard_accesses),
            cache_dir,
        ],
        cwd=root,
        capture_output=True,
        text=True,
        check=True,
    )
    return json.loads(proc.stdout)


def _grid_seconds(specs, pairs, cache_dir, workers, pipeline=True):
    """Wall-clock one full grid evaluation; returns (seconds, result)."""
    from repro.core import Experiment, WorkloadCache
    from repro.core.exec.artifacts import ArtifactCache

    cache = WorkloadCache(artifacts=ArtifactCache(cache_dir))
    exp = Experiment(workloads=specs, prefetchers=pairs, cache=cache)
    t0 = time.perf_counter()
    # Baselines and parity gates pin workers explicitly (workers=1 is the
    # serial reference path); only the scheduler section passes
    # workers=None to measure the cost model's own choice.
    result = exp.run(workers=workers, pipeline=pipeline)
    return time.perf_counter() - t0, result


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    # One persistent JAX compilation cache shared by this process, every
    # spawned worker and the gauge children: the untimed stage phase below
    # warms it, so no timed measurement pays for XLA compiles.  The
    # threshold must be set before the first jax import.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    from repro.core.exec.compile_cache import use_compile_cache
    from repro.core.exec.scheduler import jax_device

    use_compile_cache()
    if argv and argv[0] == "--_score-sharded":
        return _sharded_child(argv[1:])
    device, kind = jax_device()
    if device != "cpu":
        # Worker pools and the peak-RSS gauge children all import JAX, and
        # a chip serves one process: nothing here can run as specified.
        print(
            f"[bench] refusing to run on {device} ({kind}): this harness "
            "measures host-CPU worker pools and spawns JAX child processes, "
            "but this process holds the chip. Run chip_smoke.py there.",
            file=sys.stderr,
        )
        return 2
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny CI grid (1 kernel x 1 dataset x 3 prefetchers)",
    )
    ap.add_argument("--kernels", default=None, help="comma list (default: per mode)")
    ap.add_argument("--datasets", default=None, help="comma list (default: per mode)")
    ap.add_argument(
        "--prefetchers", default=None, help="comma list (default: per mode)"
    )
    ap.add_argument("--workers", default="1,2,4", help="comma list of pool sizes")
    ap.add_argument("--out-dir", default=".", help="where BENCH_<date>.json lands")
    ap.add_argument(
        "--cache-dir",
        default=None,
        help="workload artifact cache root (default: fresh temp dir, removed "
        "after the run, so the serial baseline is guaranteed cold)",
    )
    args = ap.parse_args(argv)

    cache_dir = args.cache_dir or tempfile.mkdtemp(prefix="repro-bench-cache-")
    own_cache_dir = args.cache_dir is None

    from repro.core import WorkloadSpec
    from repro.core.exec.scheduler import rows_equal
    from repro.core.exec.timers import collect_stages, stage, time_s
    from repro.core.experiment import score_prefetcher
    from repro.core.registry import resolve_prefetchers
    from repro.memsim import current_engine, simulate_demand, use_engine

    if args.kernels or args.datasets:
        default = SMOKE_CELLS if args.smoke else FULL_CELLS
        if args.kernels:
            kernels = args.kernels.split(",")
        else:
            kernels = sorted({k for k, _, _ in default})
        if args.datasets:
            datasets = args.datasets.split(",")
        else:
            datasets = sorted({d for _, d, _ in default})
        cells = [(k, d, 0) for k in kernels for d in datasets]
    else:
        cells = SMOKE_CELLS if args.smoke else FULL_CELLS
    if args.prefetchers:
        names = args.prefetchers.split(",")
    else:
        names = PREFETCHERS if args.smoke else GRID_PREFETCHERS
    workers_list = [int(w) for w in args.workers.split(",")]

    specs = [WorkloadSpec(k, d, seed=s) for k, d, s in cells]
    pairs = resolve_prefetchers(names)
    stage_names = args.prefetchers.split(",") if args.prefetchers else PREFETCHERS

    # --- pipeline stage breakdown (one cold build; also warms JAX/XLA —
    # compiles land in the shared persistent cache, so neither the serial
    # baseline nor any worker pays for them inside a timed region).
    print(f"[bench] stages: building {specs[0].kernel}/{specs[0].dataset} cold")
    with collect_stages() as stages:
        trace = specs[0].build()
    score_s = {}
    score_stages: dict = {}
    for name, gen in resolve_prefetchers(stage_names):
        with collect_stages(into=score_stages):
            score_s[name] = time_s(partial(score_prefetcher, trace, name, gen))
        print(f"[bench] score {name}: {score_s[name]:.2f}s")

    def _level_times(d):
        # Only stages that actually ran: the fused engine (default) emits
        # one cache_pass[fused] stage per hierarchy walk, the per-level
        # engines emit l1/l2/llc — schema v9 drops the always-zero keys
        # (notably score_cache_pass[l1]; scoring never touches L1).
        return {
            lvl: d[f"cache_pass[{lvl}]"]
            for lvl in ("l1", "l2", "llc", "fused")
            if f"cache_pass[{lvl}]" in d
        }

    # --- trace-emitter gate + micro-bench (schema v4): the batched
    # whole-run emitter must be bit-identical to the per-iteration
    # reference on a full workload build, and the emission micro cases
    # time both emitters over the same app runs.
    import numpy as np

    from repro.apps import get_kernel
    from repro.apps.trace import TraceConfig, trace_run, use_emitter
    from repro.graphs import make_dataset

    ref_stages: dict = {}
    with collect_stages(into=ref_stages), use_emitter("reference"):
        ref_trace = specs[0].build()
    emitter_ok = all(
        np.array_equal(getattr(trace, f), getattr(ref_trace, f))
        for f in ("block", "array_id", "elem", "iter_id", "epoch_id")
    )
    print(
        f"[bench] trace emitter batched vs reference: "
        f"{'ok' if emitter_ok else 'DIVERGED'} "
        f"(trace_emit {stages.get('trace_emit', 0.0):.3f}s vs "
        f"{ref_stages.get('trace_emit', 0.0):.3f}s)"
    )
    if not emitter_ok:
        print(
            "[bench] EMITTER FAILURE: batched whole-run emission diverges "
            "from the per-iteration reference",
            file=sys.stderr,
        )
    del ref_trace

    emitter_micro = []
    for mk, md in EMITTER_MICRO:
        ks = get_kernel(mk)
        g = make_dataset(md, weighted=ks.weighted)
        run = ks.run(g)
        cfg = TraceConfig(g.num_vertices, g.num_edges)
        accesses = len(trace_run(run, cfg))
        sample = {}
        for emitter in ("batched", "reference"):
            with use_emitter(emitter):
                trace_run(run, cfg)  # warm (pull-body caches)
                sample[emitter] = time_s(
                    partial(trace_run, run, cfg), repeats=5
                )
        emitter_micro.append(
            {
                "workload": f"{mk}/{md}",
                "iters": run.num_iters,
                "accesses": accesses,
                "batched_s": sample["batched"],
                "reference_s": sample["reference"],
                "speedup": sample["reference"] / sample["batched"]
                if sample["batched"] > 0
                else float("inf"),
            }
        )
        print(
            f"[bench] emit {mk}/{md} ({run.num_iters} iters): "
            f"batched {sample['batched']:.4f}s vs reference "
            f"{sample['reference']:.4f}s "
            f"(x{emitter_micro[-1]['speedup']:.1f})"
        )

    # --- engine/reference divergence gate: the set-parallel engine's hit
    # masks and one scored cell must be bit-identical to the serial scan.
    engine = current_engine()
    engine_ok = True
    if engine != "reference":
        blocks, iters, cfg = trace.block, trace.iter_id, trace.spec.hierarchy
        prof = trace.profile
        with use_engine("reference"):
            ref_prof = simulate_demand(blocks, iters, cfg)
            pname, pgen = resolve_prefetchers(stage_names[:1])[0]
            ref_row = score_prefetcher(trace, pname, pgen).row()
        eng_row = score_prefetcher(trace, pname, pgen).row()
        engine_ok = bool(
            np.array_equal(prof.l1_hit, ref_prof.l1_hit)
            and np.array_equal(prof.l2_hit, ref_prof.l2_hit)
            and np.array_equal(prof.llc_hit, ref_prof.llc_hit)
        ) and rows_equal([eng_row], [ref_row])
        print(
            f"[bench] engine {engine} vs reference: "
            f"{'ok' if engine_ok else 'DIVERGED'}"
        )
        if not engine_ok:
            print(
                f"[bench] ENGINE FAILURE: {engine} diverges from the "
                "serial lax.scan reference",
                file=sys.stderr,
            )

    # --- fused hierarchy engine (schema v9): compile-warmed demand+score
    # A/B of the fused path (one L1→L2→LLC carried scan per demand walk,
    # one vmapped launch per level for the scored prefetcher family)
    # against the per-level set_parallel cascade, on the stage cell.
    # Both sides run once untimed first so the comparison measures steady
    # state, not per-shape XLA compiles.  The committed speedup is the
    # ratio of engine-attributable seconds — the demand_sim stage plus
    # the scoring cache_pass[*] stages; prefetch-stream generation and
    # the host-side outcome analysis are engine-independent and would
    # only dilute the ratio toward 1.  Bit identity is gated into the
    # exit code: the fused profile masks and scored rows — batched AND
    # looped — must equal the per-level engine's, which the engine gate
    # above ties to the serial reference oracle.
    from repro.core.experiment import score_prefetchers_batched
    from repro.core.obs import spans as obs

    stage_pairs = resolve_prefetchers(stage_names)
    blocks, iters, cfg = trace.block, trace.iter_id, trace.spec.hierarchy
    rows_box: dict = {}

    def _demand():
        with stage("demand_sim"):
            return simulate_demand(blocks, iters, cfg)

    def _score_loop():
        rows_box["loop"] = [
            score_prefetcher(trace, n_, g_).row() for n_, g_ in stage_pairs
        ]

    def _score_batched():
        rows_box["batched"] = [
            m.row() for m in score_prefetchers_batched(trace, stage_pairs)
        ]

    def _engine_seconds(d):
        # demand_sim already contains its nested cache_pass[*] stages
        # (stage timers accumulate flat, so both keys cover the same
        # seconds) — summing both would double-count the demand walk.
        if "demand_sim" in d:
            return d["demand_sim"]
        return sum(v for k, v in d.items() if k.startswith("cache_pass["))

    def _timed_stages(fn):
        d: dict = {}
        t0 = time.perf_counter()
        with collect_stages(into=d):
            fn()
        return time.perf_counter() - t0, d

    with use_engine("set_parallel"):
        _demand(), _score_loop()  # warm per-shape compiles untimed
        pl_demand_w, pl_demand_stages = _timed_stages(_demand)
        pl_score_w, pl_score_stages = _timed_stages(_score_loop)
        pl_rows = rows_box["loop"]
    pl_demand_s = _engine_seconds(pl_demand_stages)
    pl_score_s = _engine_seconds(pl_score_stages)
    with use_engine("fused"):
        _demand(), _score_batched()  # warm per-shape compiles untimed
        # the metrics registry opens after the warm-up, so the committed
        # launch counters cover exactly one timed demand+score pass
        with obs.metrics_registry() as fused_metrics:
            fu_demand_w, fu_demand_stages = _timed_stages(_demand)
            fu_score_w, fu_score_stages = _timed_stages(_score_batched)
        fu_batch_rows = rows_box["batched"]
        _score_loop()
        fu_loop_rows = rows_box["loop"]
        fu_prof = simulate_demand(blocks, iters, cfg)
    fu_demand_s = _engine_seconds(fu_demand_stages)
    fu_score_s = _engine_seconds(fu_score_stages)
    fused_speedup = (pl_demand_s + pl_score_s) / max(
        fu_demand_s + fu_score_s, 1e-9
    )
    if engine == "fused":
        # The engine gate above already compared the fused engine (the
        # session default, used to build `trace`) against the reference.
        fused_vs_ref = engine_ok
    else:
        with use_engine("reference"):
            fr_prof = simulate_demand(blocks, iters, cfg)
            fr_row = score_prefetcher(trace, *stage_pairs[0]).row()
        with use_engine("fused"):
            ff_row = score_prefetcher(trace, *stage_pairs[0]).row()
        fused_vs_ref = bool(
            np.array_equal(fu_prof.l1_hit, fr_prof.l1_hit)
            and np.array_equal(fu_prof.l2_hit, fr_prof.l2_hit)
            and np.array_equal(fu_prof.llc_hit, fr_prof.llc_hit)
        ) and rows_equal([ff_row], [fr_row])
    fused_ok = (
        fused_vs_ref
        and rows_equal(pl_rows, fu_loop_rows)
        and rows_equal(pl_rows, fu_batch_rows)
    )
    print(
        f"[bench] fused demand+score engine-s: "
        f"{fu_demand_s + fu_score_s:.2f}s vs per-level "
        f"{pl_demand_s + pl_score_s:.2f}s (x{fused_speedup:.2f}, wall "
        f"{fu_demand_w + fu_score_w:.2f}s vs "
        f"{pl_demand_w + pl_score_w:.2f}s, "
        f"identity {'ok' if fused_ok else 'DIVERGED'}, "
        f"launches {fused_metrics.counter('fused.launches'):.0f}, "
        f"batched streams "
        f"{fused_metrics.counter('fused.batched_streams'):.0f})"
    )
    if not fused_ok:
        print(
            "[bench] FUSED FAILURE: fused hierarchy engine diverges from "
            "the per-level/reference path",
            file=sys.stderr,
        )
    del trace

    # --- end-to-end grid wall-clock: serial cold, then warm cache per pool.
    parity = True
    try:
        serial_cold_s, serial_result = _grid_seconds(specs, pairs, cache_dir, 1)
        serial_rows = serial_result.rows()
        print(f"[bench] grid serial cold: {serial_cold_s:.1f}s")

        warm = {}
        for w in workers_list:
            seconds, result = _grid_seconds(specs, pairs, cache_dir, w)
            warm[str(w)] = seconds
            same = rows_equal(serial_rows, result.rows())
            parity = parity and same
            print(
                f"[bench] grid workers={w} warm: {seconds:.1f}s "
                f"(x{serial_cold_s / seconds:.1f} vs serial cold, "
                f"parity {'ok' if same else 'FAILED'})"
            )
            if not same:
                print(
                    f"[bench] PARITY FAILURE: workers={w} results diverge "
                    "from serial",
                    file=sys.stderr,
                )

        # --- scheduler (schema v7): the cost-aware workers=None default,
        # measured warm against the pinned workers=1 reference, then a
        # cold A/B of the cost-aware schedule vs the legacy phased
        # workers=2 schedule on fresh artifact dirs.  The committed
        # SchedDecision documents *why* this host went serial or parallel.
        # Schema v8: the auto warm run executes under a cross-process span
        # tracer — workers append spans to per-pid JSONL files under the
        # trace dir, the parent merges them, and the merged summary +
        # metrics snapshot + run manifest are committed below.
        from repro.core.obs import spans as obs

        sched_stages: dict = {}
        trace_dir = tempfile.mkdtemp(prefix="repro-bench-trace-")
        try:
            with obs.trace(dir=trace_dir) as tracer:
                with collect_stages(into=sched_stages):
                    auto_warm_s, auto_result = _grid_seconds(
                        specs, pairs, cache_dir, None
                    )
            auto_run_trace = tracer.result
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        auto_parity = rows_equal(serial_rows, auto_result.rows())
        parity = parity and auto_parity
        warm1 = warm.get("1")
        auto_not_slower = (
            True if warm1 is None else auto_warm_s <= warm1 * SCHED_AUTO_TOL
        )
        auto_sched = auto_result.sched or {}
        print(
            f"[bench] sched auto warm: {auto_warm_s:.1f}s "
            f"(mode {auto_sched.get('mode')}, "
            f"workers {auto_sched.get('workers')}, "
            f"parity {'ok' if auto_parity else 'FAILED'})"
        )
        if not auto_parity:
            print(
                "[bench] PARITY FAILURE: workers=None results diverge "
                "from serial",
                file=sys.stderr,
            )
        if not auto_not_slower:
            print(
                f"[bench] SCHED FAILURE: auto warm {auto_warm_s:.1f}s is "
                f"slower than workers=1 warm {warm1:.1f}s "
                f"(tolerance x{SCHED_AUTO_TOL})",
                file=sys.stderr,
            )

        cold_ab = {}
        for label, ab_workers, ab_pipe in (
            ("auto_pipelined", None, True),
            ("phased_workers2", 2, False),
        ):
            ab_dir = tempfile.mkdtemp(prefix="repro-bench-ab-")
            try:
                ab_s, ab_result = _grid_seconds(
                    specs, pairs, ab_dir, ab_workers, pipeline=ab_pipe
                )
            finally:
                shutil.rmtree(ab_dir, ignore_errors=True)
            ab_same = rows_equal(serial_rows, ab_result.rows())
            parity = parity and ab_same
            cold_ab[label] = {"wallclock_s": ab_s, "parity": ab_same}
            if ab_result.sched is not None:
                cold_ab[label]["decision"] = ab_result.sched
            print(
                f"[bench] sched cold A/B {label}: {ab_s:.1f}s "
                f"(parity {'ok' if ab_same else 'FAILED'})"
            )
            if not ab_same:
                print(
                    f"[bench] PARITY FAILURE: cold {label} results diverge "
                    "from serial",
                    file=sys.stderr,
                )
        cold_not_slower = (
            cold_ab["auto_pipelined"]["wallclock_s"]
            <= cold_ab["phased_workers2"]["wallclock_s"] * SCHED_COLD_TOL
        )
        if not cold_not_slower:
            print(
                "[bench] SCHED FAILURE: cost-aware cold schedule lost to "
                "the legacy phased workers=2 schedule "
                f"(tolerance x{SCHED_COLD_TOL})",
                file=sys.stderr,
            )

        # --- streaming subsystem (schema v3): one small multi-epoch
        # stream cell, with the stream-protocol stage breakdown and a
        # serial-vs-parallel parity gate of its own.
        from repro.stream import SlidingWindow, StreamSpec

        stream_spec = StreamSpec(
            "pgd", "comdblp", SlidingWindow(), epochs=STREAM_EPOCHS
        )
        stream_pairs = resolve_prefetchers(STREAM_PREFETCHERS)
        print(
            f"[bench] stream: {STREAM_EPOCHS}-epoch sliding-window "
            f"{stream_spec.kernel}/{stream_spec.dataset} cold"
        )
        stream_stages: dict = {}
        with collect_stages(into=stream_stages):
            stream_cold_s, stream_result = _grid_seconds(
                [stream_spec], stream_pairs, cache_dir, 1
            )
        stream_rows = stream_result.rows()
        print(f"[bench] stream serial cold: {stream_cold_s:.1f}s")
        stream_par_stages: dict = {}
        with collect_stages(into=stream_par_stages):
            stream_warm_s, stream_par = _grid_seconds(
                [stream_spec], stream_pairs, cache_dir, 2
            )
        stream_parity = rows_equal(stream_rows, stream_par.rows())
        parity = parity and stream_parity
        print(
            f"[bench] stream workers=2 warm: {stream_warm_s:.1f}s "
            f"(parity {'ok' if stream_parity else 'FAILED'}, overlap "
            f"{stream_par_stages.get('pipeline_overlap', 0.0):.2f}s)"
        )
        if not stream_parity:
            print(
                "[bench] PARITY FAILURE: stream workers=2 results diverge "
                "from serial",
                file=sys.stderr,
            )

        # --- delta-aware epoch trace reuse (schema v7): a zero-churn
        # stream's epochs share one content key, so the cold run emits
        # epoch 0 once and serves epochs 1..E-1 from the artifact cache
        # (trace_reuse counts them); a warm rerun reuses every epoch.
        # The reused trace must be bit-identical to a from-scratch
        # re-emission of the same epoch.
        from repro.core import WorkloadCache
        from repro.stream import UniformChurn

        reuse_spec = StreamSpec(
            "pgd",
            "comdblp",
            UniformChurn(init_frac=1.0, del_frac=0.0, add_frac=0.0),
            epochs=STREAM_EPOCHS,
        )
        print(
            f"[bench] stream reuse: zero-churn {STREAM_EPOCHS}-epoch "
            f"{reuse_spec.kernel}/{reuse_spec.dataset} cold"
        )
        reuse_cold_s, reuse_cold = _grid_seconds(
            [reuse_spec], stream_pairs, cache_dir, 1
        )
        reuse_warm_s, reuse_warm = _grid_seconds(
            [reuse_spec], stream_pairs, cache_dir, 1
        )
        reuse_counts_ok = (
            reuse_cold.trace_reuse == STREAM_EPOCHS - 1
            and reuse_warm.trace_reuse == STREAM_EPOCHS
        )
        from repro.core.exec.artifacts import ArtifactCache as _AC

        last_epoch = reuse_spec.epoch_specs()[-1]
        reused_trace = WorkloadCache(artifacts=_AC(cache_dir)).get_or_build(
            last_epoch
        )
        fresh_trace = last_epoch.build()
        reuse_bits_ok = all(
            np.array_equal(getattr(reused_trace, f), getattr(fresh_trace, f))
            for f in (
                "block",
                "array_id",
                "elem",
                "iter_id",
                "epoch_id",
                "nl_blocks",
                "nl_pos",
            )
        )
        del reused_trace, fresh_trace
        reuse_ok = reuse_counts_ok and reuse_bits_ok
        print(
            f"[bench] stream reuse: cold {reuse_cold_s:.1f}s "
            f"(trace_reuse {reuse_cold.trace_reuse}) warm {reuse_warm_s:.1f}s "
            f"(trace_reuse {reuse_warm.trace_reuse}), reuse-vs-re-emission "
            f"{'ok' if reuse_bits_ok else 'DIVERGED'}"
        )
        if not reuse_ok:
            print(
                "[bench] REUSE FAILURE: delta-aware epoch reuse diverges "
                "from re-emission or miscounts cache hits",
                file=sys.stderr,
            )

        # --- serving subsystem (schema v5): K concurrent tenants on one
        # shared LLC, throughput (queries/sec) + a parity gate of its own.
        from repro.serve import ServeSpec, TenantSpec

        serve_pairs = resolve_prefetchers(SERVE_PREFETCHERS)
        serve_by_tenants = {}
        for n_tenants in SERVE_TENANT_COUNTS:
            tenants = tuple(
                TenantSpec(k, d, seed=s)
                for k, d, s in SERVE_TENANTS[:n_tenants]
            )
            serve_spec = ServeSpec(tenants=tenants)
            print(f"[bench] serve: K={n_tenants} tenants on tiny, cold")
            serve_stages: dict = {}
            with collect_stages(into=serve_stages):
                serve_cold_s, serve_result = _grid_seconds(
                    [serve_spec], serve_pairs, cache_dir, 1
                )
            serve_rows = serve_result.rows()
            serve_warm_s, _ = _grid_seconds(
                [serve_spec], serve_pairs, cache_dir, 1
            )
            _, serve_par = _grid_seconds(
                [serve_spec], serve_pairs, cache_dir, 2
            )
            serve_same = rows_equal(serve_rows, serve_par.rows())
            parity = parity and serve_same
            qps = n_tenants / serve_warm_s if serve_warm_s > 0 else 0.0
            print(
                f"[bench] serve K={n_tenants}: cold {serve_cold_s:.1f}s "
                f"warm {serve_warm_s:.1f}s ({qps:.2f} queries/s, "
                f"parity {'ok' if serve_same else 'FAILED'})"
            )
            if not serve_same:
                print(
                    f"[bench] PARITY FAILURE: serve K={n_tenants} workers=2 "
                    "results diverge from serial",
                    file=sys.stderr,
                )
            serve_by_tenants[str(n_tenants)] = {
                "tenants": [
                    f"{k}/{d}#s{s}" for k, d, s in SERVE_TENANTS[:n_tenants]
                ],
                "stages_s": {
                    "serve_interleave": serve_stages.get("serve_interleave", 0.0),
                    "serve_llc": serve_stages.get("serve_llc", 0.0),
                    "serve_score": serve_stages.get("serve_score", 0.0),
                },
                "wallclock_s": {
                    "serial_cold": serve_cold_s,
                    "warm_serial": serve_warm_s,
                },
                "queries_per_s": qps,
                "parallel_matches_serial": serve_same,
            }

        # --- sharded paper-scale subsystem (schema v6): the streaming
        # scorer must be bit-identical to the unsharded path, and (full
        # mode) peak RSS must be flat in trace length.
        from repro.core.exec.artifacts import ArtifactCache
        from repro.core.exec.sharded import (
            ShardedSpec,
            ensure_shards,
            score_sharded,
        )

        acache = ArtifactCache(cache_dir)
        par_kernel, par_dataset = (
            ("bfs", "tiny") if args.smoke else ("bfs", "comdblp")
        )
        par_base = WorkloadSpec(par_kernel, par_dataset, seed=0)
        shard_pairs = resolve_prefetchers(SHARD_PREFETCHERS)
        print(
            f"[bench] sharded parity: {par_kernel}/{par_dataset} at "
            f"shard_accesses={SHARD_PARITY_ACCESSES}"
        )
        shard_stages: dict = {}
        with collect_stages(into=shard_stages):
            t0 = time.perf_counter()
            sh_scored = score_sharded(
                ShardedSpec(
                    base=par_base, shard_accesses=SHARD_PARITY_ACCESSES
                ),
                shard_pairs,
                acache,
            )
            shard_score_s = time.perf_counter() - t0
        par_trace = par_base.build()
        un_rows = [
            score_prefetcher(par_trace, n, g).row() for n, g in shard_pairs
        ]
        del par_trace
        sharded_parity = rows_equal(un_rows, [m.row() for _, m in sh_scored])
        parity = parity and sharded_parity
        print(
            f"[bench] sharded vs unsharded rows: "
            f"{'ok' if sharded_parity else 'DIVERGED'} "
            f"({shard_score_s:.1f}s sharded)"
        )
        if not sharded_parity:
            print(
                "[bench] PARITY FAILURE: sharded streaming scoring diverges "
                "from the unsharded path",
                file=sys.stderr,
            )

        shard_rss = None
        rss_flat = True
        if not args.smoke:
            gauge = {}
            for gk, gd, gs in SHARD_RSS_CELLS:
                gspec = ShardedSpec(
                    base=WorkloadSpec(gk, gd, seed=gs),
                    shard_accesses=SHARD_GAUGE_ACCESSES,
                )
                t0 = time.perf_counter()
                ensure_shards(gspec, acache)
                mat_s = time.perf_counter() - t0
                gauge[gd] = {"kernel": gk, "materialize_s": round(mat_s, 2)}
                print(f"[bench] sharded gauge: {gk}/{gd} built {mat_s:.1f}s")
            # One discarded warm-up run per cell lands every shard-shape's
            # XLA compiles in the shared persistent compilation cache —
            # including each cell's unique remainder-shard bucket — so the
            # measured children pay zero compile-time memory spikes and
            # the gauge compares streaming-state footprints only.
            for gk, gd, gs in SHARD_RSS_CELLS:
                _gauge_child_run(gk, gd, gs, SHARD_GAUGE_ACCESSES, cache_dir)
            for gk, gd, gs in SHARD_RSS_CELLS:
                rep = _gauge_child_run(
                    gk, gd, gs, SHARD_GAUGE_ACCESSES, cache_dir
                )
                gauge[gd].update(rep)
                print(
                    f"[bench] sharded gauge: {gk}/{gd} "
                    f"{rep['accesses']} accesses / {rep['shards']} shards: "
                    f"peak {rep['maxrss_kb']} KiB ({rep['score_s']:.1f}s)"
                )
            ratio = (
                gauge["road-8m"]["maxrss_kb"] / gauge["comdblp"]["maxrss_kb"]
            )
            rss_flat = abs(ratio - 1.0) <= SHARD_RSS_TOL
            shard_rss = {
                "cells": gauge,
                "ratio_vs_comdblp": round(ratio, 4),
                "tolerance": SHARD_RSS_TOL,
                "flat": rss_flat,
            }
            print(
                f"[bench] sharded gauge: peak-RSS ratio {ratio:.3f} "
                f"({'flat' if rss_flat else 'NOT FLAT'} within "
                f"{SHARD_RSS_TOL:.0%})"
            )
            if not rss_flat:
                print(
                    "[bench] RSS FAILURE: sharded scoring peak RSS grows "
                    "with trace length",
                    file=sys.stderr,
                )
    finally:
        if own_cache_dir:
            shutil.rmtree(cache_dir, ignore_errors=True)

    out = {
        "schema": SCHEMA_VERSION,
        "date": date.today().isoformat(),
        "smoke": args.smoke,
        "host": {
            "python": platform.python_version(),
            "platform": platform.platform(),
            "cpus": os.cpu_count(),
        },
        "grid": {
            "workloads": [f"{k}/{d}#s{s}" for k, d, s in cells],
            "prefetchers": names,
            "cells": len(specs) * len(names),
        },
        "cache_engine": engine,
        "stages_s": {
            "trace_gen": stages.get("trace_gen", 0.0),
            "trace_emit": stages.get("trace_emit", 0.0),
            "demand_sim": stages.get("demand_sim", 0.0),
            "cache_pass": _level_times(stages),
            "score": score_s,
            "score_cache_pass": _level_times(score_stages),
        },
        # Schema v4: batched whole-run emission vs the per-iteration
        # reference — full-build stage times, parity, and the micro cases.
        "trace_emitter": {
            "rebuild_reference_s": {
                "trace_gen": ref_stages.get("trace_gen", 0.0),
                "trace_emit": ref_stages.get("trace_emit", 0.0),
            },
            "micro": emitter_micro,
        },
        # Schema v9: the fused hierarchy engine — compile-warmed
        # demand+score A/B against the per-level set_parallel cascade on
        # the stage cell.  ``speedup`` is the engine-attributable ratio
        # (demand_sim stage + scoring cache_pass[*] stages; generation
        # and shared host analysis excluded), ``wall_s`` the raw wall
        # clocks of the same timed runs; launch counters cover exactly
        # the timed fused pass, and the bit-identity verdict is gated
        # into the exit code.
        "fused": {
            "cell": f"{cells[0][0]}/{cells[0][1]}#s{cells[0][2]}",
            "prefetchers": stage_names,
            "per_level_s": {"demand_sim": pl_demand_s, "score": pl_score_s},
            "fused_s": {"demand_sim": fu_demand_s, "score": fu_score_s},
            "wall_s": {
                "per_level": {"demand_sim": pl_demand_w, "score": pl_score_w},
                "fused": {"demand_sim": fu_demand_w, "score": fu_score_w},
            },
            "speedup": fused_speedup,
            "launches": fused_metrics.counter("fused.launches"),
            "batched_streams": fused_metrics.counter("fused.batched_streams"),
            "matches_reference": fused_ok,
        },
        "wallclock_s": {"serial_cold": serial_cold_s, "warm_by_workers": warm},
        "speedup_vs_serial_cold": {
            w: serial_cold_s / s for w, s in warm.items() if s > 0
        },
        # Schema v7: the cost-aware scheduler — the committed decision
        # record for this host, the auto-vs-workers=1 warm gate, and the
        # cold A/B against the legacy phased schedule.
        "scheduler": {
            "auto": {
                "decision": auto_result.sched,
                "warm_wallclock_s": auto_warm_s,
                "warm_workers1_s": warm1,
                "not_slower_than_workers1": auto_not_slower,
                "tolerance": SCHED_AUTO_TOL,
            },
            "cold_ab": {
                **cold_ab,
                "pipelined_not_slower": cold_not_slower,
                "tolerance": SCHED_COLD_TOL,
            },
            "stages_s": dict(sorted(sched_stages.items())),
        },
        # Schema v8: structured run telemetry from the auto warm run —
        # the run manifest (provenance), the merged metrics registry
        # snapshot, and the merged parent+worker span-trace summary.
        "telemetry": {
            "manifest": (auto_result.telemetry or {}).get("manifest"),
            "workload_cache": (auto_result.telemetry or {}).get(
                "workload_cache"
            ),
            "metrics": auto_run_trace.metrics,
            "trace": auto_run_trace.summary(),
        },
        # Schema v3: the streaming-subsystem cell (3-epoch sliding-window
        # stream) with the stream-protocol stage timers.
        "stream": {
            "kernel": stream_spec.kernel,
            "dataset": stream_spec.dataset,
            "epochs": STREAM_EPOCHS,
            "churn": "sliding_window",
            "prefetchers": STREAM_PREFETCHERS,
            "stages_s": {
                "update_apply": stream_stages.get("update_apply", 0.0),
                "trace_epoch": stream_stages.get("trace_epoch", 0.0),
                "table_carry": stream_stages.get("table_carry", 0.0),
                "pipeline_overlap": stream_par_stages.get(
                    "pipeline_overlap", 0.0
                ),
            },
            "wallclock_s": {
                "serial_cold": stream_cold_s,
                "warm_workers2": stream_warm_s,
            },
            "parallel_matches_serial": stream_parity,
            # Schema v7: delta-aware epoch trace reuse (zero-churn cell).
            "reuse": {
                "churn": "zero_churn",
                "epochs": STREAM_EPOCHS,
                "wallclock_s": {
                    "serial_cold": reuse_cold_s,
                    "warm_serial": reuse_warm_s,
                },
                "trace_reuse": {
                    "cold": reuse_cold.trace_reuse,
                    "warm": reuse_warm.trace_reuse,
                },
                "counts_expected": reuse_counts_ok,
                "matches_reemission": reuse_bits_ok,
            },
        },
        # Schema v5: the serving-subsystem cells (K concurrent tenants
        # over one shared LLC, both AMC table modes) with the serving
        # stage timers and the queries/sec throughput figure.
        "serve": {
            "dataset": "tiny",
            "policy": "round_robin",
            "table_modes": ["per_tenant", "shared"],
            "prefetchers": SERVE_PREFETCHERS,
            "by_tenants": serve_by_tenants,
        },
        # Schema v6: the sharded paper-scale subsystem — streaming-scoring
        # parity vs the unsharded path, the streaming stage timers, and
        # (full mode) the peak-RSS flatness gauge.
        "sharded": {
            "prefetchers": SHARD_PREFETCHERS,
            "parity_cell": f"{par_kernel}/{par_dataset}#s0",
            "parity_shard_accesses": SHARD_PARITY_ACCESSES,
            "parity_matches_unsharded": sharded_parity,
            "score_s": shard_score_s,
            "stages_s": dict(sorted(shard_stages.items())),
            "gauge_shard_accesses": SHARD_GAUGE_ACCESSES,
            "rss": shard_rss,
        },
        "parallel_matches_serial": parity,
        "engine_matches_reference": engine_ok,
        "fused_matches_reference": fused_ok,
        "emitter_matches_reference": emitter_ok,
        "sharded_rss_flat": rss_flat,
        "sched_auto_not_slower": auto_not_slower,
        "sched_cold_pipelined_not_slower": cold_not_slower,
        "trace_reuse_matches_reemission": reuse_ok,
    }
    out_dir = Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path = out_dir / f"BENCH_{out['date']}.json"
    n = 2
    while out_path.exists():
        # Keep earlier same-day runs: they are the "before" points of the
        # perf trajectory.
        out_path = out_dir / f"BENCH_{out['date']}.{n}.json"
        n += 1
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(f"[bench] wrote {out_path}")
    return (
        0
        if (
            parity
            and engine_ok
            and fused_ok
            and emitter_ok
            and rss_flat
            and auto_not_slower
            and cold_not_slower
            and reuse_ok
        )
        else 1
    )


if __name__ == "__main__":
    raise SystemExit(main())
