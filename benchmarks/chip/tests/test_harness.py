"""The harness finds its parts by name, counts whole jobs, reduces spans,
and refuses to run without a chip."""
import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np
import pytest

import run_cell
from layers import Layers, subtract
from xplane import union

BENCH = Path(run_cell.__file__).resolve().parent
CHECKOUT = BENCH.parents[1]


def test_every_cell_resolves_its_files_by_name():
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run_cell.load_cell(CHECKOUT, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["jobs"] and cell.traffic["check_jobs"] >= 1
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))


def test_adding_a_config_traffic_and_metric_needs_no_edit(tmp_path, monkeypatch):
    """A later change adds files and entries; no file that is there changes.
    A sharded traffic may name VLDP, which the check regenerates; the
    program has no streaming VLDP yet, so its run fails."""
    root = tmp_path / "benchmarks" / "chip"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / sub, root / sub)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    config = json.loads((BENCH / "configs" / "bfs-amazon-scaled.json").read_text())
    config["name"] = "bfs-other"
    (root / "configs" / "bfs-other.json").write_text(json.dumps(config))
    (root / "traffic" / "burst.json").write_text(
        json.dumps({"workload": "per_job", "jobs": [{"prefetchers": "config"}],
                    "check_jobs": 2})
    )
    (root / "traffic" / "paper.json").write_text(
        json.dumps({"workload": "sharded", "shard_accesses": 1 << 22,
                    "jobs": [{"prefetchers": "config"}], "check_jobs": 1})
    )
    (root / "metrics" / "jobs_pct.py").write_text("def read(layers):\n    return 42.0\n")
    bench["configs"].append(dict(bench["configs"][0], name="bfs-other",
                                 file="benchmarks/chip/configs/bfs-other.json"))
    bench["workloads"].append({"name": "bfs-other.burst", "config": "bfs-other",
                               "traffic": "burst", "chips": 1, "why": "test"})
    config.update(name="bfs-paper", dataset="tinyroad",
                  graph={"kind": "road", "n": 20000, "shortcut_frac": 0.0, "seed": 18})
    assert {p["registry"] for p in config["prefetchers"]} == {"amc", "vldp"}
    (root / "configs" / "bfs-paper.json").write_text(json.dumps(config))
    bench["configs"].append(dict(bench["configs"][0], name="bfs-paper",
                                 file="benchmarks/chip/configs/bfs-paper.json"))
    bench["workloads"].append({"name": "bfs-paper.paper", "config": "bfs-paper",
                               "traffic": "paper", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "jobs_pct", "unit": "%", "better": "lower",
                               "source": "program_span", "layer": "harness",
                               "moves": "accesses_per_s",
                               "workloads": ["bfs-other.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = run_cell.load_cell(tmp_path, "bfs-other.burst")
    assert cell.config["name"] == "bfs-other" and cell.traffic["check_jobs"] == 2
    assert [m["name"] for m in cell.per_layer] == ["jobs_pct"]
    assert cell.reader("jobs_pct")(None) == 42.0
    sharded = run_cell.load_cell(tmp_path, "bfs-paper.paper")
    traffic = run_cell.Traffic(sharded.config, sharded.traffic, seed=2**33 + 1)
    spec = traffic.spec(3)
    assert spec.is_sharded and spec.shard_accesses == 1 << 22
    assert spec == traffic.spec(0) and spec.base.seed == run_cell.job_seed(2**33 + 1, 0)
    assert all(p.read_bytes() == b for p, b in before.items())
    from repro.core.exec.sharded import ShardedScoringError

    scratch = tmp_path / "tmp"
    scratch.mkdir()
    monkeypatch.setattr(tempfile, "tempdir", str(scratch))
    with pytest.raises(ShardedScoringError, match="vldp"):
        run_cell.run(tmp_path, "bfs-paper.paper", 2**33 + 1, 0.01, traced=False,
                     require_chip=False, log=lambda s: None)
    assert list(scratch.iterdir()) == []


@pytest.mark.parametrize("entry", [
    {"name": "bingo", "registry": "bingo", "overrides": {}},
    {"name": "amc.small", "registry": "amc", "overrides": {"storage_fraction": 0.2}},
], ids=["bingo", "amc-storage_fraction"])
def test_a_traffic_naming_a_prefetcher_with_no_plain_generator_is_refused_at_load(
        tmp_path, entry):
    """The check could not regenerate that prefetcher's stream."""
    root = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH / "configs", root / "configs")
    (root / "traffic").mkdir()
    (root / "traffic" / "grid.json").write_text(json.dumps(
        {"workload": "per_run", "check_jobs": 1,
         "jobs": [{"prefetchers": "config"}, {"prefetchers": [entry]}]}
    ))
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": "bfs-amazon-scaled.grid", "chips": 1, "why": "t",
                           "config": "bfs-amazon-scaled", "traffic": "grid"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(run_cell.BadCell, match=entry["name"]):
        run_cell.load_cell(tmp_path, "bfs-amazon-scaled.grid")


def test_a_sharded_traffic_naming_a_derived_stream_is_refused_at_load(tmp_path):
    """The sharded path makes ``nextline2``'s stream itself, so no stream
    that the check could re-score is ever issued."""
    root = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH / "configs", root / "configs")
    (root / "traffic").mkdir()
    (root / "traffic" / "paper.json").write_text(json.dumps(
        {"workload": "sharded", "shard_accesses": 1 << 22, "check_jobs": 1,
         "jobs": [{"prefetchers": "config"},
                  {"prefetchers": [{"name": "nextline2", "registry": "nextline2",
                                    "overrides": {}}]}]}
    ))
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": "bfs-amazon-scaled.paper", "chips": 1, "why": "t",
                           "config": "bfs-amazon-scaled", "traffic": "paper"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(run_cell.BadCell, match="nextline2"):
        run_cell.load_cell(tmp_path, "bfs-amazon-scaled.paper")


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


@pytest.mark.parametrize("durations,seconds,expect_jobs", [
    ([4.0, 4.0, 4.0, 4.0], 10.0, 3),  # the third job ends past 10 s and counts
    ([12.0, 1.0], 10.0, 1),  # one long job outlasts the window
    ([1.0] * 8, 5.0, 5),
])
def test_the_window_counts_whole_jobs_to_the_last_end(durations, seconds, expect_jobs):
    clock = FakeClock()

    def job(index):
        clock.now += durations[index]
        return run_cell.Job(index, accesses=1000 * (index + 1), result=None, streams={})

    w = run_cell.window(job, 0, seconds, clock, log=print)
    assert w.jobs == expect_jobs and w.failed == 0
    assert w.seconds == pytest.approx(sum(durations[:expect_jobs]))
    assert w.job_s == pytest.approx(durations[:expect_jobs])
    assert w.accesses / w.seconds == pytest.approx(
        sum(1000 * (i + 1) for i in range(expect_jobs)) / sum(durations[:expect_jobs])
    )


def test_a_failing_job_counts_as_failed():
    clock = FakeClock()

    def job(index):
        clock.now += 2.0
        if index == 1:
            raise ValueError("boom")
        return run_cell.Job(index, 10, None, {})

    w = run_cell.window(job, 0, 5.0, clock, log=lambda s: None)
    assert w.jobs == 2 and w.failed == 1 and w.seconds == 6.0 and w.accesses == 20


@pytest.mark.parametrize("keep", [1, 3])
def test_the_window_keeps_a_sample_drawn_from_the_seed(keep):
    def kept(seed, n):
        clock = FakeClock()

        def job(index):
            clock.now += 1.0
            return run_cell.Job(index, 1, None, {})

        w = run_cell.window(job, 0, n, clock, log=print, keep=keep,
                            rng=np.random.default_rng(seed))
        return [j.index for j in w.sample]

    assert kept(7, 2) == list(range(min(keep, 2)))
    assert kept(7, 40) == kept(7, 40) and len(kept(7, 40)) == keep
    draws = {tuple(kept(seed, 40)) for seed in range(30)}
    assert len(draws) > 10  # every job can be drawn, not only the first
    assert all(list(j) == sorted(j) for j in draws)


def test_span_shares_take_unions_and_leave_out_nested_spans():
    spans = [
        ("trace_gen", 0.0, 4.0),
        ("trace_emit", 1.0, 2.0),  # nested in trace_gen: counted once
        ("cache_pass[l1]", 3.0, 5.0),  # half inside trace_gen
        ("score", 5.0, 9.0),
        ("cache_pass[l2]", 6.0, 7.0),  # nested in score
        ("cache_pass[llc]", 7.0, 7.5),
        ("trace_gen", 9.5, 12.0),  # runs past the window's end
    ]
    layers = Layers(window_s=10.0, spans=spans)
    assert layers.share(r"trace_gen|trace_emit", r"cache_pass\[.*\]|score") == (
        pytest.approx(100.0 * (3.0 + 0.5) / 10.0)
    )
    assert layers.share(r"cache_pass\[.*\]") == pytest.approx(100.0 * 3.5 / 10.0)
    assert layers.share(r"score", r"cache_pass\[.*\]") == pytest.approx(25.0)
    assert layers.share(r"trace_epoch") is None


def test_interval_arithmetic():
    assert union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert union([(0, 5)], clip=(1, 3)) == [(1, 3)]
    assert subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert subtract([(0, 1), (5, 6)], [(2, 3)]) == [(0, 1), (5, 6)]


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run_cell.py"), "--workload",
         "bfs-amazon-scaled.fresh", "--seed", str(2**33 + 5), "--seconds", "1",
         "--trace", "0"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "accelerator" in proc.stderr
