"""A ``sharded`` traffic on a tiny road lattice, on the CPU.

The cell scores AMC on ``tinyroad`` under the scaled hierarchy from a shard
store of 2^14-access shards, so one job spans many shards.  A sound run
reads every number at 0; the control (the reference under FIFO
replacement in the program's place) and each fault planted in the timed
path make ``correct`` false; the run leaves no file behind.
"""
import dataclasses
import json
import os
import shutil
import tempfile
from pathlib import Path

import numpy as np
import pytest

import run_cell

BENCH = Path(run_cell.__file__).resolve().parent
SEED = 2**32 + 4099
WORKLOAD = "bfs-tinyroad.sharded"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    chip = root / "benchmarks" / "chip"
    shutil.copytree(BENCH / "metrics", chip / "metrics")
    (chip / "configs").mkdir(parents=True)
    (chip / "traffic").mkdir()
    config = json.loads((BENCH / "configs" / "bfs-amazon-scaled.json").read_text())
    config.update(
        name="bfs-tinyroad",
        dataset="tinyroad",
        graph={"kind": "road", "n": 20000, "shortcut_frac": 0.0, "seed": 18},
        prefetchers=[p for p in config["prefetchers"] if p["name"] == "amc"],
    )
    (chip / "configs" / "bfs-tinyroad.json").write_text(json.dumps(config))
    (chip / "traffic" / "sharded.json").write_text(json.dumps(
        {"workload": "sharded", "shard_accesses": 1 << 14,
         "jobs": [{"prefetchers": "config"}], "check_jobs": 1}
    ))
    bench = json.loads((BENCH.parents[1] / "BENCHMARK.json").read_text())
    bench["configs"] = [dict(bench["configs"][0], name="bfs-tinyroad",
                             file="benchmarks/chip/configs/bfs-tinyroad.json")]
    bench["workloads"] = [{"name": WORKLOAD, "config": "bfs-tinyroad",
                           "traffic": "sharded", "chips": 1, "why": "test"}]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def tmpdir_only(tmp_path, monkeypatch):
    """Temporary files go to a directory of the test's own."""
    scratch = tmp_path / "tmp"
    scratch.mkdir()
    monkeypatch.setenv("TMPDIR", str(scratch))
    monkeypatch.setattr(tempfile, "tempdir", str(scratch))
    return scratch


def _run(checkout, control=False):
    return run_cell.run(checkout, WORKLOAD, SEED, 0.01, traced=False,
                        require_chip=False, control=control, log=lambda s: None)


def _files(root: Path) -> dict:
    return {p: p.stat().st_mtime_ns for p in root.rglob("*")}


def test_a_sound_run_is_correct_the_control_is_not_and_no_file_stays(
        checkout, tmpdir_only, monkeypatch):
    stored = []
    close = run_cell.Traffic.close

    def recording_close(self):
        stored.append(sorted(os.listdir(self.store_dir)))
        close(self)

    monkeypatch.setattr(run_cell.Traffic, "close", recording_close)
    before = _files(checkout)
    result = _run(checkout, control=True)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert all(c["value"] == 0 for c in result["checks"].values())
    control = result["control"]
    assert control["hit_mismatch"] > 0 and control["row_gap"] > 0
    assert control["stream_mismatch"] > 0
    # The store held the shards of one job of many shards, then went.
    (names,) = stored
    assert sum(n.endswith(".npz") for n in names) > 4
    assert any(n.endswith(".manifest.json") for n in names)
    assert list(tmpdir_only.iterdir()) == []
    assert _files(checkout) == before


def _alter_an_access(monkeypatch):
    """One access altered in a shard as it is written."""
    from repro.core.exec.artifacts import ArtifactCache

    real = ArtifactCache.save_shard

    def altered(self, spec, index, arrays):
        if index == 1:
            arrays = dict(arrays, block=arrays["block"].copy())
            arrays["block"][len(arrays["block"]) // 2] += 1
        return real(self, spec, index, arrays)

    monkeypatch.setattr(ArtifactCache, "save_shard", altered)


def _perturb_a_row(monkeypatch):
    import repro.core.exec.sharded as sharded

    real = sharded._metrics

    def perturbed(*a, **k):
        m = real(*a, **k)
        return dataclasses.replace(m, coverage=m.coverage * (1 + 1e-9))

    monkeypatch.setattr(sharded, "_metrics", perturbed)


def _demand_count_off_by_one(monkeypatch):
    import repro.core.exec.sharded as sharded

    real = sharded._metrics

    def off(*a, **k):
        m = real(*a, **k)
        return dataclasses.replace(m, dram_demand=m.dram_demand + 1)

    monkeypatch.setattr(sharded, "_metrics", off)


def _alter_a_spilled_miss(monkeypatch):
    """One block of the miss spill that AMC's view of the shards yields."""
    import repro.core.exec.sharded as sharded

    real = sharded._ShardedWorkloadView.amc_iteration_views

    def altered(self):
        done = False
        for view, epoch in real(self):
            if not done and len(view.miss_blocks):
                blocks = view.miss_blocks.copy()
                blocks[len(blocks) // 2] += 1
                view = dataclasses.replace(view, miss_blocks=blocks)
                done = True
            yield view, epoch

    monkeypatch.setattr(sharded._ShardedWorkloadView, "amc_iteration_views", altered)


@pytest.mark.parametrize("fault,number", [
    (_alter_an_access, "trace_mismatch"),
    (_perturb_a_row, "row_gap"),
    (_demand_count_off_by_one, "hit_mismatch"),
    (_alter_a_spilled_miss, "stream_mismatch"),
])
def test_a_fault_in_the_sharded_path_makes_the_run_incorrect(
        checkout, tmpdir_only, monkeypatch, fault, number):
    fault(monkeypatch)
    result = _run(checkout)
    assert result["correct"] is False
    assert result["checks"][number]["value"] > result["checks"][number]["limit"]
    if number == "stream_mismatch":  # generation alone: the rest read 0
        assert all(c["value"] == 0 for k, c in result["checks"].items() if k != number)
    assert list(tmpdir_only.iterdir()) == []


def test_a_demand_count_is_compared_in_place_of_masks():
    """A sharded job's rows carry its demand counts; they are compared."""
    import check

    row = {f: 1.0 for f in check.ROW_FIELDS}
    stream = (np.array([7, 9]), np.array([2, 2]), 64)

    class Ref:
        def simulate(self, seed):
            return {"blocks": np.zeros(3, np.int64), "iter_id": np.zeros(3, np.int32),
                    "eval_from": 1}, None

        def row(self, d, stream, eval_from):
            return dict(row, dram_demand=5.0, baseline_l2_misses=7.0)

        def stream(self, seed, entry):
            return stream

    job = {"workloads": [dict(seed=1, block=np.zeros(3, np.int64),
                              iter_id=np.zeros(3, np.int32), eval_from=1,
                              rows=[(dict(row, dram_demand=4.0, baseline_l2_misses=9.0),
                                     stream, {"name": "amc", "registry": "amc",
                                              "overrides": {}})])]}
    numbers = check.compare([job], Ref())
    assert numbers["hit_mismatch"] == 1 + 2 and numbers["trace_mismatch"] == 0
    assert numbers["stream_mismatch"] == 0
