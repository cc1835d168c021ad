"""Telemetry layer tests: span tracing, metrics, stage-timer shims,
cross-process merge, trace export, and the bench-diff/perf-report tools.

The stage-shim contract (ISSUE 9): ``stage()``/``collect_stages()``/
``record()`` re-exported through ``repro.core.exec.timers`` must behave
bit-identically to the pre-span implementation — including the no-op
fast path and nested-collector shadowing — while doubling as spans when
a tracer is active.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.core.exec.timers import collect_stages, record, stage
from repro.core.obs import spans as obs
from repro.core.obs.metrics import (
    MetricsRegistry,
    bucket_of,
    histogram_quantile,
    merge_snapshots,
)

sys.path.insert(0, ".")


@pytest.fixture(autouse=True)
def _clean_obs_state():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


# ------------------------------------------------------------ stage shims


def test_record_accumulates_and_is_noop_when_inactive():
    record("orphan", 2.0)  # no collector: must not raise or record anywhere
    with collect_stages() as times:
        record("overlap", 1.5)
        record("overlap", 0.5)
        record("count")  # default value 1.0
    assert times == {"overlap": 2.0, "count": 1.0}
    record("late", 9.0)  # collector closed again
    assert "late" not in times and "orphan" not in times


def test_nested_collectors_shadow_and_restore():
    with collect_stages() as outer:
        with stage("a"):
            pass
        with collect_stages() as inner:
            with stage("b"):
                pass
            record("r", 3.0)
        # Inner collector closed: the outer one is active again.
        with stage("c"):
            pass
    assert set(outer) == {"a", "c"}
    assert set(inner) == {"b", "r"} and inner["r"] == 3.0


def test_nested_collector_restores_outer_on_exception():
    with collect_stages() as outer:
        with pytest.raises(RuntimeError):
            with collect_stages():
                raise RuntimeError("boom")
        with stage("after"):
            pass
    assert "after" in outer


def test_stage_noop_fast_path_records_nothing():
    assert not obs.tracing()
    with stage("free"):
        pass  # no collector, no tracer, no registry: nothing observable
    assert obs.current_metrics() is None


def test_stage_spans_share_the_exact_collector_durations():
    """The one perf_counter delta feeds both the stage dict and the span,
    so the span-derived totals equal the collector dict bit-for-bit."""
    with collect_stages() as times:
        with obs.trace() as t:
            for _ in range(3):
                with stage("phase"):
                    pass
            with stage("other"):
                pass
    totals = t.result.stage_totals()
    assert totals["phase"] == times["phase"]
    assert totals["other"] == times["other"]
    assert len(t.result.by_name("phase")) == 3


def test_span_parentage_and_attrs():
    with obs.trace() as t:
        with obs.span("outer", kernel="pgd") as sp:
            assert sp is not None and sp.attrs["kernel"] == "pgd"
            with obs.span("inner", epoch=2):
                pass
            sp.attrs["cache"] = "hit"  # late attribute attach
    outer = t.result.by_name("outer")[0]
    inner = t.result.by_name("inner")[0]
    assert inner.parent_id == outer.span_id
    assert outer.parent_id is None
    assert outer.attrs == {"kernel": "pgd", "cache": "hit"}
    assert outer.trace_id == inner.trace_id == t.trace_id


def test_span_is_noop_without_tracer():
    with obs.span("nothing", x=1) as sp:
        assert sp is None


# ------------------------------------------------------------------ metrics


def test_metrics_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    assert not reg
    reg.inc("hits")
    reg.inc("hits", 2.0)
    reg.set_gauge("pool", 4)
    reg.observe("lat", 0.5)
    reg.observe("lat", 2.0)
    assert reg and reg.counter("hits") == 3.0
    assert reg.ratio("hits", "misses") == 1.0
    assert reg.ratio("absent", "also_absent") is None
    h = reg.snapshot()["histograms"]["lat"]
    assert h["count"] == 2 and h["sum"] == 2.5
    assert h["min"] == 0.5 and h["max"] == 2.0
    assert histogram_quantile(h, 1.0) == 2.0
    assert bucket_of(0.0) == 0 and bucket_of(1e-6) == 0
    assert bucket_of(2e-6) < bucket_of(1.0) < bucket_of(100.0)


def test_merge_snapshots_sums_counters_and_merges_histograms():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("n", 1)
    b.inc("n", 2)
    a.set_gauge("g", 1)
    b.set_gauge("g", 2)
    a.observe("h", 1.0)
    b.observe("h", 4.0)
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    assert merged["counters"]["n"] == 3.0
    assert merged["gauges"]["g"] == 2.0  # last writer in pid order
    h = merged["histograms"]["h"]
    assert h["count"] == 2 and h["sum"] == 5.0 and h["max"] == 4.0


def test_metrics_helpers_route_to_active_registry():
    with obs.trace() as t, obs.metrics_registry() as reg:
        obs.inc("c", 2)
        obs.observe("h", 0.1)
        obs.set_gauge("g", 7)
        with stage("timed"):
            pass
    assert reg.counter("c") == 2.0
    assert reg.gauges["g"] == 7.0
    assert [s.name for s in t.spans] == ["timed"]
    obs.inc("c")  # registry closed: no-op
    assert reg.counter("c") == 2.0


# -------------------------------------------------------- trace dir merge


def _write_worker_file(dir, pid, spans, metrics_lines=()):
    path = dir / f"spans-worker-{pid}.jsonl"
    with open(path, "w") as f:
        for s in spans:
            f.write(json.dumps(s) + "\n")
        for line in metrics_lines:
            f.write(json.dumps(line) + "\n")
    return path


def _fake_span(pid, seq, ts, name="w", trace_id="t1"):
    return {
        "name": name,
        "trace_id": trace_id,
        "span_id": f"{pid:x}-{seq:x}",
        "parent_id": None,
        "ts": ts,
        "dur": 0.001,
        "pid": pid,
        "proc": "worker",
        "attrs": {},
    }


def test_run_trace_merge_is_deterministic_and_ordered(tmp_path):
    _write_worker_file(tmp_path, 300, [_fake_span(300, 1, 50)])
    _write_worker_file(tmp_path, 4, [_fake_span(4, 1, 200), _fake_span(4, 2, 10)])
    a = obs.RunTrace.load(tmp_path)
    b = obs.RunTrace.load(tmp_path)
    assert a.as_dict() == b.as_dict()  # merge is a pure function of files
    assert [(s.ts, s.pid) for s in a.spans] == [(10, 4), (50, 300), (200, 4)]
    assert a.processes() == [(4, "worker"), (300, "worker")]


def test_run_trace_merge_keeps_last_metrics_per_pid_and_sums_across(tmp_path):
    m1 = {"counters": {"n": 1.0}, "gauges": {}, "histograms": {}}
    m2 = {"counters": {"n": 5.0}, "gauges": {}, "histograms": {}}
    _write_worker_file(
        tmp_path,
        4,
        [_fake_span(4, 1, 10)],
        [
            {"kind": "metrics", "pid": 4, "proc": "worker", "seq": 1, "metrics": m1},
            {"kind": "metrics", "pid": 4, "proc": "worker", "seq": 2, "metrics": m2},
        ],
    )
    _write_worker_file(
        tmp_path,
        300,
        [_fake_span(300, 1, 20)],
        [
            {
                "kind": "metrics",
                "pid": 300,
                "proc": "worker",
                "seq": 1,
                "metrics": m1,
            }
        ],
    )
    rt = obs.RunTrace.load(tmp_path)
    # Cumulative snapshots: last per pid (5), summed across pids (+1).
    assert rt.metrics["counters"]["n"] == 6.0


def test_run_trace_merge_drops_corrupt_tail_lines(tmp_path):
    path = _write_worker_file(tmp_path, 4, [_fake_span(4, 1, 10)])
    with open(path, "a") as f:
        f.write('{"name": "torn-wri')  # killed mid-write
    rt = obs.RunTrace.load(tmp_path)
    assert len(rt.spans) == 1


def test_run_trace_save_read_roundtrip(tmp_path):
    with obs.trace(dir=tmp_path / "t") as t:
        with obs.span("a", k=1):
            pass
        obs.inc("c", 2)
    rt = t.result
    path = rt.save(tmp_path / "run.json")
    back = obs.RunTrace.read(path)
    assert back.as_dict() == rt.as_dict()
    with pytest.raises(ValueError):
        bogus = tmp_path / "bogus.json"
        bogus.write_text('{"schema": "other"}')
        obs.RunTrace.read(bogus)


def test_tracer_finish_is_idempotent(tmp_path):
    with obs.trace(dir=tmp_path) as t:
        with obs.span("a"):
            pass
    first = t.finish()
    assert first is t.result and t.finish() is first
    # Exactly one copy of the span on disk despite repeated finishes.
    assert len(obs.RunTrace.load(tmp_path).spans) == 1


# ------------------------------------------------- cross-process tracing


def test_worker_env_probe_joins_parent_trace(tmp_path):
    """A spawned process finding REPRO_TRACE_DIR set appends its spans to
    its own JSONL file; the parent's merge sees both processes."""
    with obs.trace(dir=tmp_path) as t:
        with obs.span("parent_work"):
            pass
        child = (
            "from repro.core.obs import spans as obs\n"
            "obs.inc('child.counter', 3)\n"
            "with obs.span('child_work', shard=1):\n"
            "    pass\n"
            "obs.flush_worker_metrics()\n"
        )
        env = dict(os.environ)
        env[obs.SPAN_DIR_ENV] = str(tmp_path)
        env[obs.TRACE_ID_ENV] = t.trace_id
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
        )
        subprocess.run(
            [sys.executable, "-c", child], check=True, env=env, timeout=120
        )
    rt = t.result
    assert {proc for _, proc in rt.processes()} == {"main", "worker"}
    child_span = rt.by_name("child_work")[0]
    assert child_span.trace_id == t.trace_id
    assert child_span.attrs == {"shard": 1}
    assert child_span.pid != os.getpid()
    assert rt.metrics["counters"]["child.counter"] == 3.0


def test_experiment_tracing_serial_matches_workers2(tmp_path):
    """Results are bit-identical with tracing active, serial vs pool, and
    the pool trace covers parent + worker processes."""
    from repro.core import ArtifactCache, Experiment, WorkloadCache
    from repro.core.exec.scheduler import rows_equal

    def fresh():
        return Experiment(
            kernels=["pgd"],
            datasets=["tiny"],
            prefetchers=["amc", "nextline2"],
            cache=WorkloadCache(artifacts=ArtifactCache(tmp_path / "arts")),
        )

    with obs.trace(dir=tmp_path / "serial") as ts:
        serial = fresh().run(workers=1)
    with obs.trace(dir=tmp_path / "pool") as tp:
        pooled = fresh().run(workers=2)
    assert rows_equal(serial.rows(), pooled.rows())

    assert {p for _, p in ts.result.processes()} == {"main"}
    procs = tp.result.processes()
    assert {p for _, p in procs} == {"main", "worker"}
    assert len(procs) >= 2
    # Worker-side scoring spans joined the parent's trace id.
    cell = tp.result.by_name("score_cell")[0]
    assert cell.trace_id == tp.trace_id
    # Both runs saw the same grid: same scored cells, same span names.
    names = {"experiment_run", "score_cell", "build_workload"}
    assert names <= {s.name for s in ts.result.spans}
    # The pooled run reuses the serial run's artifact cache, so workers
    # load rather than rebuild: materialize/run_task spans, no build.
    assert {"experiment_run", "score_cell", "materialize", "run_task"} <= {
        s.name for s in tp.result.spans
    }
    assert len(ts.result.by_name("score_cell")) == len(
        tp.result.by_name("score_cell")
    )
    # Merge determinism: re-loading the span dir reproduces the RunTrace.
    assert obs.RunTrace.load(tmp_path / "pool").as_dict() == {
        **tp.result.as_dict(),
        "manifest": None,
    }
    # Telemetry attach: manifest provenance + trace linkage.
    assert pooled.telemetry["trace_id"] == tp.trace_id
    assert pooled.telemetry["manifest"]["trace_schema"] == obs.TRACE_SCHEMA
    assert pooled.telemetry["workload_cache"]["hits"] >= 0


# ------------------------------------------- spans inside scoring and passes

# The span each new span opens inside: scoring pieces inside ``score``
# (the next-line baseline's merge and classification run in
# ``demand_sim``), the parts of a cache pass inside its ``cache_pass[...]``.
_NEW_SPAN_PARENTS = {
    "score.generate[amc]": {"score"},
    "score.generate[vldp]": {"score"},
    "score.evaluate": {"score"},
    "prefetch.merge": {"score", "demand_sim"},
    "prefetch.classify": {"score", "demand_sim"},
    "cache_pass.group": {"cache_pass[l1]", "cache_pass[l2]", "cache_pass[llc]"},
    "cache_pass.device": {"cache_pass[l1]", "cache_pass[l2]", "cache_pass[llc]"},
    "cache_pass.scatter": {"cache_pass[l1]", "cache_pass[l2]", "cache_pass[llc]"},
}


def _tiny_bfs_experiment():
    from repro.core import Experiment

    return Experiment(kernels=["bfs"], datasets=["tiny"], prefetchers=["amc", "vldp"])


@pytest.mark.parametrize("engine", ["set_parallel", "pallas"])
def test_scoring_and_cache_pass_spans_open_inside_their_layers(engine):
    from repro.memsim import use_engine

    with use_engine(engine), obs.trace() as t:
        _tiny_bfs_experiment().run(workers=1)
    by_id = {s.span_id: s for s in t.spans}
    parents: dict = {}
    for s in t.spans:
        if s.name in _NEW_SPAN_PARENTS:
            parents.setdefault(s.name, set()).add(by_id[s.parent_id].name)
    assert set(parents) == set(_NEW_SPAN_PARENTS)
    for name, seen in parents.items():
        assert seen <= _NEW_SPAN_PARENTS[name], name
    assert {"score", "demand_sim"} <= parents["prefetch.merge"]
    # Each pass is split into its three parts, in order, with nothing else.
    for s in t.spans:
        if s.name.startswith("cache_pass["):
            kids = [k.name for k in t.spans
                    if k.parent_id == s.span_id and k.name != "jax_compile"]
            assert kids == ["cache_pass.group", "cache_pass.device",
                            "cache_pass.scatter"]


def test_a_first_compile_is_a_span_and_a_repeat_call_is_not():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 3 + 1)
    with obs.trace() as t:
        with obs.span("outer"):
            f(jnp.ones(37)).block_until_ready()
        first = list(t.spans)
        f(jnp.ones(37)).block_until_ready()
    compiles = [s for s in first if s.name == "jax_compile"]
    events = {s.attrs["event"] for s in compiles}
    assert obs.COMPILE_PHASE_PREFIX + "backend_compile_duration" in events
    assert all(e.startswith(obs.COMPILE_PHASE_PREFIX) for e in events)
    outer = next(s for s in first if s.name == "outer")
    for s in compiles:
        assert s.parent_id == outer.span_id and s.dur > 0
        assert outer.ts <= s.ts and s.ts + s.dur * 1e9 <= outer.ts + outer.dur * 1e9 + 1e6
    assert [s.name for s in t.spans[len(first):]] == []


def test_new_span_sites_record_nothing_without_a_tracer(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.memsim import use_engine

    entered = []

    class Annotation:
        def __init__(self, name, **kwargs):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(obs, "TraceAnnotation", Annotation)
    with use_engine("set_parallel"), collect_stages() as times:
        _tiny_bfs_experiment().run(workers=1)
        jax.jit(lambda x: x - 5)(jnp.ones(41)).block_until_ready()
    assert obs.current_tracer() is None
    assert entered == []
    # The new sites are spans, not stages: the stage dict keeps its keys.
    assert not [k for k in times if "." in k or k == "jax_compile"]

    with use_engine("set_parallel"), obs.trace() as t:
        _tiny_bfs_experiment().run(workers=1)
    assert sorted(entered) == sorted(s.name for s in t.spans)


def _host_events(xplane_path: str):
    """``{name: [start_ns]}`` of the host events of a profiler trace, on
    the wall clock (``time.time_ns``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    start = dict(data.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"
    ]
    out: dict = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(start + e.start_ns)
    return out


def test_program_spans_land_on_the_profiler_host_plane(tmp_path):
    import glob

    import jax
    from repro.memsim import use_engine

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # program spans only, not every call
    with use_engine("set_parallel"), jax.profiler.trace(
        str(tmp_path), profiler_options=options
    ), obs.trace() as t:
        _tiny_bfs_experiment().run(workers=1)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = _host_events(path)
    names = {s.name for s in t.spans}
    assert {"score", "score.evaluate", "cache_pass.device", "jax_compile"} <= names
    for s in t.spans:
        starts = host.get(s.name, [])
        assert any(abs(h - s.ts) < 1_000_000 for h in starts), (s.name, s.attrs)


# ----------------------------------------------------------- trace export


def test_chrome_trace_export(tmp_path):
    from tools.trace_export import chrome_trace, main

    with obs.trace(dir=tmp_path / "t") as t:
        with obs.span("outer", kernel="pgd"):
            with stage("score"):
                pass
    doc = chrome_trace(t.result)
    assert doc["schema"] == "chrome-trace"
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    slices = [e for e in events if e["ph"] == "X"]
    assert len(meta) == 1 and meta[0]["name"] == "process_name"
    assert {e["name"] for e in slices} == {"outer", "score"}
    for e in slices:
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["pid"] == os.getpid()
    inner = next(e for e in slices if e["name"] == "score")
    outer = next(e for e in slices if e["name"] == "outer")
    assert inner["args"]["parent"] == outer["id"]
    json.dumps(doc)  # must be directly serializable

    saved = t.result.save(tmp_path / "run.json")
    out = tmp_path / "chrome.json"
    assert main([str(saved), "-o", str(out)]) == 0
    assert json.loads(out.read_text())["trace_id"] == t.trace_id
    # Directory input works too, and an empty trace is a loud error.
    assert main([str(tmp_path / "t"), "-o", str(out)]) == 0
    empty = tmp_path / "empty"
    empty.mkdir()
    assert main([str(empty), "-o", str(out)]) == 1


# ------------------------------------------------------- bench-diff tools


def test_bench_sort_key_orders_numeric_suffixes():
    from benchmarks.perf_report import bench_sort_key

    paths = [
        "BENCH_2026-08-01.10.json",
        "BENCH_2026-08-01.2.json",
        "BENCH_2026-08-01.json",
        "BENCH_2026-07-30.json",
    ]
    ordered = sorted(paths, key=bench_sort_key)
    assert ordered == [
        "BENCH_2026-07-30.json",
        "BENCH_2026-08-01.json",
        "BENCH_2026-08-01.2.json",
        "BENCH_2026-08-01.10.json",
    ]


def _bench_doc(smoke, stages, grid=None):
    return {
        "schema": 8,
        "smoke": smoke,
        "grid": grid or {"workloads": ["pgd/tiny#s0"], "prefetchers": ["amc"]},
        "stages_s": stages,
    }


def test_bench_diff_flags_regressions_and_honors_floor(tmp_path):
    from tools.bench_diff import comparable, diff_stages

    old = _bench_doc(False, {"score": 1.0, "noise": 0.001, "gone": 1.0})
    new = _bench_doc(False, {"score": 2.0, "noise": 0.004, "added": 1.0})
    assert comparable(old, new)
    assert not comparable(old, _bench_doc(True, {}))
    d = diff_stages(old, new, threshold=1.5, min_seconds=0.05)
    regressed = {r["stage"] for r in d["regressions"]}
    # score breached ratio+floor; noise breached ratio only (under floor).
    assert regressed == {"score"}
    by_stage = {r["stage"]: r for r in d["rows"]}
    assert by_stage["gone"]["new_s"] is None
    assert by_stage["added"]["old_s"] is None


def test_bench_diff_cli_picks_comparable_pair_and_gates(tmp_path):
    from tools.bench_diff import main

    # Newest doc is a smoke run; the full run in between must be skipped
    # when picking its baseline.
    (tmp_path / "BENCH_2026-01-01.json").write_text(
        json.dumps(_bench_doc(True, {"score": 1.0}))
    )
    (tmp_path / "BENCH_2026-01-02.json").write_text(
        json.dumps(_bench_doc(False, {"score": 50.0}))
    )
    (tmp_path / "BENCH_2026-01-03.json").write_text(
        json.dumps(_bench_doc(True, {"score": 1.01}))
    )
    out = tmp_path / "diff.json"
    assert main(["--root", str(tmp_path), "--out", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert doc["old"] == "BENCH_2026-01-01.json"
    assert doc["new"] == "BENCH_2026-01-03.json"

    # A genuine regression in the newest pair exits non-zero.
    (tmp_path / "BENCH_2026-01-04.json").write_text(
        json.dumps(_bench_doc(True, {"score": 9.0}))
    )
    assert main(["--root", str(tmp_path), "--threshold", "1.5"]) == 1
    # No comparable baseline at all: pass with a note.
    solo = tmp_path / "solo"
    solo.mkdir()
    (solo / "BENCH_2026-01-01.json").write_text(
        json.dumps(_bench_doc(True, {"score": 1.0}))
    )
    assert main(["--root", str(solo)]) == 0
