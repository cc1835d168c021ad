"""``correct`` against the plain reference, on a small configuration.

A sound run reads every number at 0.  The control (the reference under
FIFO replacement in the program's place) and each fault planted in the
timed path below make ``correct`` false.  A fault in a prefetcher's
generation shows in ``stream_mismatch`` alone: ``row_gap`` reads 0, since
the reference re-scores the stream that the timed path issued.
"""
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

import run_cell

BENCH = Path(run_cell.__file__).resolve().parent
SEED = 2**31 + 977


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout with one cell: BFS on a 3,000-vertex power-law graph."""
    root = tmp_path_factory.mktemp("checkout")
    chip = root / "benchmarks" / "chip"
    for sub in ("traffic", "metrics"):
        shutil.copytree(BENCH / sub, chip / sub)
    config = json.loads((BENCH / "configs" / "bfs-amazon-scaled.json").read_text())
    config.update(
        name="bfs-tiny",
        dataset="tiny",
        graph={"kind": "powerlaw", "n": 3000, "m": 9000, "gamma": 2.2, "seed": 21},
    )
    (chip / "configs").mkdir()
    (chip / "configs" / "bfs-tiny.json").write_text(json.dumps(config))
    bench = json.loads((BENCH.parents[1] / "BENCHMARK.json").read_text())
    bench["configs"] = [dict(bench["configs"][0], name="bfs-tiny",
                             file="benchmarks/chip/configs/bfs-tiny.json")]
    bench["workloads"] = [
        {"name": "bfs-tiny.fresh", "config": "bfs-tiny", "traffic": "fresh",
         "chips": 1, "why": "test"},
        {"name": "bfs-tiny.amc-sweep", "config": "bfs-tiny", "traffic": "amc-sweep",
         "chips": 1, "why": "test"},
    ]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(checkout, workload="bfs-tiny.fresh", control=False):
    return run_cell.run(checkout, workload, SEED, 0.01, traced=False,
                        require_chip=False, control=control, log=lambda s: None)


@pytest.mark.parametrize("workload", ["bfs-tiny.fresh", "bfs-tiny.amc-sweep"])
def test_a_sound_run_is_correct_and_the_control_is_not(checkout, workload):
    result = _run(checkout, workload, control=True)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert all(c["value"] == 0 for c in result["checks"].values())
    assert list(result)[-1] == "checks"
    control = result["control"]
    assert control["hit_mismatch"] > 0 and control["row_gap"] > 0
    assert control["stream_mismatch"] > 0
    assert any(v > run_cell.check.LIMITS[k] for k, v in control.items())


def _alter_trace(monkeypatch):
    import repro.apps.trace as trace

    real = trace.trace_run

    def altered(run, cfg=None):
        rt = real(run, cfg)
        rt.block[len(rt.block) // 2] += 1  # one access, where it is emitted
        return rt

    monkeypatch.setattr("repro.core.driver.trace_run", altered)


def _flip_a_hit(monkeypatch):
    import repro.core.driver as driver

    real = driver.simulate_demand

    def flipped(blocks, iter_id, cfg, *a, **k):
        profile = real(blocks, iter_id, cfg, *a, **k)
        profile.llc_hit[len(profile.llc_hit) // 3] ^= True
        return profile

    monkeypatch.setattr(driver, "simulate_demand", flipped)


def _state_unchanged(monkeypatch):
    """The LLC pass returns its starting state: no demand ever hits there."""
    import repro.core.driver as driver

    real = driver.simulate_demand

    def cold(blocks, iter_id, cfg, *a, **k):
        profile = real(blocks, iter_id, cfg, *a, **k)
        profile.llc_hit[:] = False
        return profile

    monkeypatch.setattr(driver, "simulate_demand", cold)


def _half_the_batch(monkeypatch):
    """Scoring sees half of each prefetch stream."""
    import repro.memsim.hierarchy as hierarchy

    real = hierarchy._merge_prefetch_stream

    def half(profile, pf_blocks, pf_pos, pf_issuer):
        keep = np.arange(len(pf_blocks)) % 2 == 0
        issuer = None if pf_issuer is None else np.asarray(pf_issuer)[keep]
        return real(profile, np.asarray(pf_blocks)[keep], np.asarray(pf_pos)[keep],
                    issuer)

    monkeypatch.setattr(hierarchy, "_merge_prefetch_stream", half)


def _alter_a_row(monkeypatch):
    import repro.core.experiment as experiment

    real = experiment.evaluate

    def altered(*a, **k):
        m = real(*a, **k)
        m.coverage *= 1 + 1e-9  # the answer, where it is produced
        return m

    monkeypatch.setattr(experiment, "evaluate", altered)


def _vldp_drops_one(monkeypatch):
    """The program's VLDP drops one prefetch as it filters its candidates."""
    import repro.core.prefetchers.spatial as spatial

    real = spatial._window_dedupe

    def dropping(blocks, pos, window):
        keep = real(blocks, pos, window)
        keep[np.flatnonzero(keep)[len(np.flatnonzero(keep)) // 2]] = False
        return keep

    monkeypatch.setattr(spatial, "_window_dedupe", dropping)


def _amc_one_entry_later(monkeypatch):
    """AMC's replay issues the first entry it matches one access later."""
    from repro.core.amc.prefetcher import AMCPrefetcher

    real_generate, real_prefetch = AMCPrefetcher.generate, AMCPrefetcher._prefetch
    pending = []

    def generate(self, workload, storage=None):
        pending[:] = [True]
        return real_generate(self, workload, storage)

    def later(self, it, rec, storage):
        issued = real_prefetch(self, it, rec, storage)
        if issued is None or not pending:
            return issued
        pending.clear()
        at = np.searchsorted(rec.trigger_vid, it.target_vid)
        hit = at < len(rec.trigger_vid)
        hit[hit] = rec.trigger_vid[at[hit]] == it.target_vid[hit]
        entry = at[np.argmax(hit)]
        pos = issued[1].copy()
        pos[: rec.nmiss[entry]] += 1
        return issued[0], pos

    monkeypatch.setattr(AMCPrefetcher, "generate", generate)
    monkeypatch.setattr(AMCPrefetcher, "_prefetch", later)


GENERATION_FAULTS = (_vldp_drops_one, _amc_one_entry_later)


@pytest.mark.parametrize("fault", [_alter_trace, _flip_a_hit, _state_unchanged,
                                   _half_the_batch, _alter_a_row, *GENERATION_FAULTS])
def test_a_fault_in_the_timed_path_makes_the_run_incorrect(checkout, monkeypatch, fault):
    fault(monkeypatch)
    result = _run(checkout)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
    if fault in GENERATION_FAULTS:  # what the three other numbers cannot see
        checks = {k: c["value"] for k, c in result["checks"].items()}
        assert checks["stream_mismatch"] > 0
        assert checks["row_gap"] == checks["trace_mismatch"] == checks["hit_mismatch"] == 0
