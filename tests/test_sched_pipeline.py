"""Cost-aware scheduler + overlap pipeline + delta-aware epoch reuse.

Three contracts from the async-pipeline scheduler work:

- The cost model's serial-vs-pool decision is deterministic for fixed
  inputs, degrades to serial on one core or when spawn overhead exceeds
  the predicted parallel gain, and caps the pool by memory.
- Pipelined execution (score tasks dispatched as builds land) is
  byte-identical to serial phased execution across every spec kind —
  plain grid, stream, serve, and sharded — in one mixed run.
- Delta-aware reuse returns bit-identical traces to re-emission: a
  zero-churn epoch is a cache hit (one build, shared content key), a
  churned epoch is a miss, and reuse is surfaced as
  ``ExperimentResult.trace_reuse`` identically in serial and pooled runs.
"""
import numpy as np
import pytest

from repro.core import ArtifactCache, Experiment, WorkloadCache
from repro.core.driver import WorkloadSpec
from repro.core.exec import scheduler
from repro.core.exec.scheduler import TaskCost, decide, estimate_cost, rows_equal
from repro.core.exec.sharded import ShardedSpec
from repro.core.experiment import score_prefetcher
from repro.core.registry import resolve_prefetchers
from repro.serve import ServeSpec, TenantSpec
from repro.stream import SlidingWindow, StreamSpec, UniformChurn

TINY = "tiny"
ZERO_CHURN = UniformChurn(init_frac=1.0, del_frac=0.0, add_frac=0.0)


def _cost(total_s, *, measured=True, resident=1e6):
    return TaskCost(
        spec=None,
        build_s=total_s / 2,
        score_s=total_s / 2,
        resident_bytes=resident,
        measured=measured,
    )


# ------------------------------------------------------------- cost model


def test_decide_is_deterministic_for_fixed_inputs():
    costs = [_cost(30.0), _cost(10.0), _cost(5.0)]
    a = decide(costs, cores=4, mem_bytes=1 << 30)
    b = decide(costs, cores=4, mem_bytes=1 << 30)
    assert a == b  # frozen dataclass equality: every field identical


def test_decide_serial_on_single_core():
    d = decide([_cost(100.0), _cost(100.0)], cores=1)
    assert d.mode == "serial" and d.workers == 1
    assert "single core" in d.reason


def test_decide_serial_when_pool_overhead_exceeds_gain():
    # Two sub-second tasks: any pool pays seconds of spawn for nothing.
    d = decide([_cost(0.3), _cost(0.3)], cores=8)
    assert d.mode == "serial" and d.workers == 1
    assert d.est_pool_s is not None and d.est_pool_s >= d.est_serial_s


def test_decide_pool_when_makespan_beats_serial():
    costs = [_cost(40.0), _cost(40.0), _cost(40.0), _cost(40.0)]
    d = decide(costs, cores=4, mem_bytes=1 << 40)
    assert d.mode == "pipeline" and d.workers == 4
    assert d.est_pool_s < d.est_serial_s


def test_decide_memory_caps_pool_width():
    # Four 1 GiB-resident tasks but only ~2 GiB available: width <= 2.
    costs = [_cost(40.0, resident=float(1 << 30)) for _ in range(4)]
    d = decide(costs, cores=8, mem_bytes=(1 << 31) + (1 << 20))
    assert d.workers <= 2
    tight = decide(costs, cores=8, mem_bytes=1 << 30)
    assert tight.mode == "serial" and "memory" in tight.reason


def test_estimate_cost_prefers_artifact_metadata(tmp_path):
    arts = ArtifactCache(tmp_path)
    spec = WorkloadSpec(kernel="pgd", dataset=TINY)
    cold = estimate_cost(spec, 2, arts)
    assert not cold.measured and cold.build_s > 0 and cold.score_s > 0
    # A materialized artifact switches the estimate to measured size and
    # replaces the build cost with the (much cheaper) load cost.
    arts.root.mkdir(parents=True, exist_ok=True)
    arts.path_for(spec).write_bytes(b"x" * 120_000)
    warm = estimate_cost(spec, 2, arts)
    assert warm.measured and warm.build_s < cold.build_s


def test_estimate_cost_prefers_measured_sidecar(tmp_path):
    """Recorded per-task seconds beat every constant-based estimate:
    ``build_s`` prices a rebuild when only the sidecar survived, and
    ``score_s_per_prefetcher`` scales exactly with the prefetcher count."""
    arts = ArtifactCache(tmp_path)
    spec = WorkloadSpec(kernel="pgd", dataset=TINY)
    assert arts.load_cost(spec) is None  # absent == None, not {}

    # record_cost merges per field; latest measurement wins.
    arts.record_cost(spec, build_s=12.5)
    arts.record_cost(spec, score_s_per_prefetcher=0.75)
    arts.record_cost(spec, build_s=10.0)
    assert arts.load_cost(spec) == {
        "build_s": 10.0,
        "score_s_per_prefetcher": 0.75,
    }

    # No artifact on disk: the recorded build_s replaces the cold
    # constant-based estimate and marks the cost as measured.
    cost = estimate_cost(spec, 2, arts)
    assert cost.measured
    assert cost.build_s == 10.0
    assert cost.score_s == pytest.approx(0.75 * 2)
    assert estimate_cost(spec, 3, arts).score_s == pytest.approx(0.75 * 3)

    # A materialized artifact demotes build to a load estimate (cheaper
    # than the recorded rebuild), but scoring still uses the sidecar.
    arts.path_for(spec).write_bytes(b"x" * 120_000)
    warm = estimate_cost(spec, 2, arts)
    assert warm.build_s < 10.0
    assert warm.score_s == pytest.approx(0.75 * 2)

    # A corrupt sidecar reads as absent, falling back to constants.
    arts.cost_path(spec).write_text("not json")
    assert arts.load_cost(spec) is None
    assert estimate_cost(spec, 2, arts).score_s != pytest.approx(0.75 * 2)


def test_plan_execution_deterministic_with_injected_host(tmp_path):
    arts = ArtifactCache(tmp_path)
    specs = [
        WorkloadSpec(kernel="pgd", dataset="road-ca"),
        WorkloadSpec(kernel="pgd", dataset="google"),
    ]
    a = scheduler.plan_execution(specs, 2, arts, cores=4, mem_bytes=1 << 40)
    b = scheduler.plan_execution(specs, 2, arts, cores=4, mem_bytes=1 << 40)
    assert a == b and a.mode == "pipeline"
    assert scheduler.plan_execution(specs, 2, arts, cores=1).mode == "serial"


def test_run_on_single_core_resolves_serial(monkeypatch, tmp_path):
    """The bench-host case: cpus == 1 -> ``run(workers=None)`` executes
    serial in-process (no spawn pool) and records the decision."""
    monkeypatch.setattr("os.cpu_count", lambda: 1)
    cache = WorkloadCache(artifacts=ArtifactCache(tmp_path))
    result = Experiment(
        workloads=[WorkloadSpec(kernel="pgd", dataset=TINY)],
        prefetchers=["nextline2"],
        cache=cache,
    ).run()
    assert result.sched is not None
    assert result.sched["mode"] == "serial" and result.sched["workers"] == 1
    assert result.sched["cores"] == 1
    # Serial runs keep the eager dict workloads mapping — proof no pool
    # path ran.
    assert isinstance(result.workloads, dict)
    # An explicitly forced worker count records no decision.
    forced = Experiment(
        workloads=[WorkloadSpec(kernel="pgd", dataset=TINY)],
        prefetchers=["nextline2"],
        cache=cache,
    ).run(workers=1)
    assert forced.sched is None


def _on_chip(monkeypatch):
    """Make the scheduler's backend probe report a TPU (JAX itself stays
    on the CPU, so the run still executes here)."""
    monkeypatch.setattr(scheduler, "jax_device", lambda: ("tpu", "TPU v5 lite"))


def test_accelerator_backend_resolves_serial(monkeypatch, tmp_path):
    """One process holds the chip: ``workers=None`` never opens a pool
    there, whatever the cost model would say, and the reason names the
    device."""
    _on_chip(monkeypatch)
    arts = ArtifactCache(tmp_path)
    specs = [
        WorkloadSpec(kernel="pgd", dataset="road-ca"),
        WorkloadSpec(kernel="pgd", dataset="google"),
    ]
    d = scheduler.plan_execution(specs, 2, arts, cores=64, mem_bytes=1 << 40)
    assert d.mode == "serial" and d.workers == 1
    assert "tpu" in d.reason and "TPU v5 lite" in d.reason
    result = Experiment(
        workloads=[WorkloadSpec(kernel="pgd", dataset=TINY)],
        prefetchers=["nextline2"],
        cache=WorkloadCache(artifacts=arts),
    ).run()
    assert result.sched["mode"] == "serial" and result.sched["workers"] == 1
    assert "TPU v5 lite" in result.sched["reason"]
    assert isinstance(result.workloads, dict)  # no pool path ran


def test_accelerator_backend_refuses_worker_pool(monkeypatch, tmp_path):
    _on_chip(monkeypatch)
    exp = Experiment(
        workloads=[WorkloadSpec(kernel="pgd", dataset=TINY)],
        prefetchers=["nextline2"],
        cache=WorkloadCache(artifacts=ArtifactCache(tmp_path)),
    )
    with pytest.raises(RuntimeError, match="workers=2 spawns JAX processes"):
        exp.run(workers=2)


def test_compile_cache_placement(monkeypatch, tmp_path):
    """An external ``JAX_COMPILATION_CACHE_DIR`` is left to JAX alone;
    without one the cache is a fixed directory inside the checkout."""
    import pathlib

    import jax

    from repro.core.exec import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV_VAR)
        checkout = pathlib.Path(__file__).resolve().parents[1]
        path = compile_cache.use_compile_cache()
        assert path == str(checkout / ".jax_cache")
        assert compile_cache.use_compile_cache() == path  # fixed across calls
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ------------------------------------------------- delta-aware trace reuse


@pytest.fixture(scope="module")
def reuse_arts(tmp_path_factory):
    return ArtifactCache(tmp_path_factory.mktemp("reuse-artifacts"))


def test_zero_churn_epochs_reuse_one_build(reuse_arts):
    """Unchanged graph => cache hit; the reused trace is bit-identical to
    a fresh re-emission, and scoring it gives identical metrics."""
    spec = StreamSpec(kernel="pgd", dataset=TINY, churn=ZERO_CHURN, epochs=3)
    cache = WorkloadCache(artifacts=reuse_arts)
    result = Experiment(
        workloads=[spec], prefetchers=["amc", "nextline2"], cache=cache
    ).run(workers=1)
    assert cache.builds == 1  # epochs 1..2 hit epoch 0's content key
    assert result.trace_reuse == 2
    # Reuse == re-emission, bit for bit.
    es = spec.epoch_specs()
    reused = cache.get_or_build(es[2])
    fresh = es[2].build()
    for field in (
        "block", "array_id", "epoch_id", "iter_id", "elem",
        "nl_blocks", "nl_pos",
    ):
        np.testing.assert_array_equal(
            getattr(reused, field), getattr(fresh, field)
        )
    ((name, gen),) = resolve_prefetchers(["amc"])
    m_reused = score_prefetcher(reused, name, gen)
    m_fresh = score_prefetcher(fresh, name, gen)
    assert rows_equal([m_reused.row()], [m_fresh.row()])
    # A warm rerun reuses every epoch.
    warm_cache = WorkloadCache(artifacts=reuse_arts)
    warm = Experiment(
        workloads=[spec], prefetchers=["amc", "nextline2"], cache=warm_cache
    ).run(workers=1)
    assert warm_cache.builds == 0 and warm.trace_reuse == 3
    assert rows_equal(result.rows(), warm.rows())


def test_churned_epochs_are_cache_misses(tmp_path):
    spec = StreamSpec(kernel="pgd", dataset=TINY, churn=SlidingWindow(), epochs=3)
    cache = WorkloadCache(artifacts=ArtifactCache(tmp_path))
    result = Experiment(
        workloads=[spec], prefetchers=["nextline2"], cache=cache
    ).run(workers=1)
    assert cache.builds == 3  # every epoch's graph changed: no reuse
    assert result.trace_reuse == 0


def test_in_memory_content_alias_dedupes_across_streams():
    """Two streams over the same (unchanged) graph content share one
    in-memory build even without an artifact store — the within-run
    dedupe satellite: persist-vs-reset comparisons and epoch-count
    variations cost one emission."""
    a = StreamSpec(kernel="pgd", dataset=TINY, churn=ZERO_CHURN, epochs=2,
                   lifecycle="persist")
    b = StreamSpec(kernel="pgd", dataset=TINY, churn=ZERO_CHURN, epochs=3,
                   lifecycle="reset")
    cache = WorkloadCache()  # no artifacts: pure in-memory aliasing
    result = Experiment(
        workloads=[a, b], prefetchers=["nextline2"], cache=cache
    ).run(workers=1)
    # 5 epoch specs (2 + 3, all distinct as specs), one real emission.
    assert cache.builds == 1
    assert cache.reuses == 4  # the other four epochs are content aliases
    assert result.trace_reuse == 4
    # The aliased traces score like the original but stay bound to their
    # own spec (retargeted copies, not one shared object).
    ea, eb = a.epoch_specs()[1], b.epoch_specs()[2]
    assert ea != eb
    ta, tb = cache.get_or_build(ea), cache.get_or_build(eb)
    np.testing.assert_array_equal(ta.block, tb.block)
    assert ta.spec == ea and tb.spec == eb and ta.spec != tb.spec


# ------------------------------------- pipelined == serial, all spec kinds


def test_pipelined_mixed_grid_matches_serial(tmp_path):
    """The headline parity property: grid + stream + serve + sharded specs
    in ONE run, serial vs pipelined pool vs phased pool — byte-identical
    rows everywhere, and reuse counts match serial vs pooled."""
    specs = [
        WorkloadSpec(kernel="pgd", dataset=TINY),
        ShardedSpec(base=WorkloadSpec(kernel="bfs", dataset=TINY),
                    shard_accesses=4096),
        StreamSpec(kernel="pgd", dataset=TINY, churn=ZERO_CHURN, epochs=2),
        ServeSpec(tenants=(TenantSpec("pgd", TINY), TenantSpec("cc", TINY))),
    ]
    pf = ["amc", "nextline2"]
    serial = Experiment(
        workloads=specs,
        prefetchers=pf,
        cache=WorkloadCache(artifacts=ArtifactCache(tmp_path / "serial")),
    ).run(workers=1)

    arts = ArtifactCache(tmp_path / "wl")
    piped = Experiment(
        workloads=specs, prefetchers=pf, cache=WorkloadCache(artifacts=arts)
    ).run(workers=2)
    assert rows_equal(serial.rows(), piped.rows())
    assert piped.trace_reuse == serial.trace_reuse == 1  # zero-churn epoch

    phased = Experiment(
        workloads=specs, prefetchers=pf, cache=WorkloadCache(artifacts=arts)
    ).run(workers=2, pipeline=False)
    assert rows_equal(serial.rows(), phased.rows())
    # Warm pooled rerun: every epoch comes from the content-keyed store.
    warm = Experiment(
        workloads=specs, prefetchers=pf, cache=WorkloadCache(artifacts=arts)
    ).run(workers=2)
    assert rows_equal(serial.rows(), warm.rows())
    assert warm.trace_reuse == 2


def test_materialize_pipeline_dedupes_in_flight_builds(tmp_path):
    """Identical-content epoch specs collapse to ONE pool build task."""
    spec = StreamSpec(kernel="pgd", dataset=TINY, churn=ZERO_CHURN, epochs=3)
    arts = ArtifactCache(tmp_path)
    pipe = scheduler.MaterializePipeline(
        spec.epoch_specs(), workers=2, artifacts=arts
    )
    try:
        assert pipe.n_specs == 3
        assert pipe.n_built == 1 and pipe.n_reused == 2
        for es in spec.epoch_specs():
            pipe.wait(es)
            assert arts.has(es)
    finally:
        pipe.close()
    # Fully warm: no pool at all, everything reused.
    warm = scheduler.MaterializePipeline(
        spec.epoch_specs(), workers=2, artifacts=arts
    )
    warm.close()
    assert warm.n_built == 0 and warm.n_reused == 3
    assert warm._stack is None  # no spawn pool was opened
