"""Decide ``correct``: the timed path's answers against the plain reference.

Four numbers, each an exact comparison with the limit 0:

- ``trace_mismatch``: accesses whose line, or iteration, differs between
  the simulator's emitted trace and the reference's, plus the difference
  in length and in the first scored position;
- ``hit_mismatch``: L1, L2 and LLC demand hit bits that differ; for a
  sharded job, which keeps no per-access masks, the gaps between the demand
  counts over the scored window that each row carries (``DEMAND_COUNTS``)
  and the reference's;
- ``row_gap``: the largest relative gap over every field of every scored
  row (speedup, coverage, accuracy, counts and traffic);
- ``stream_mismatch``: for every scored row, the prefetches in which the
  stream that the timed path issued differs from the stream of the plain
  generator of the row's registry entry and overrides
  (``reference/prefetchers.py``): the positions where (line, position)
  differ once both are in issue order, plus the difference in length, plus
  1 where the metadata bytes differ.  Issue order is the stable position
  order that scoring applies, or (position, line) order for a generator
  whose order within a position the simulator leaves undefined.

The reference takes the configuration and the job's seed.  It scores each
row with the stream that the timed path issued, so that a fault in
generation shows in ``stream_mismatch`` and one in scoring in ``row_gap``.
Its cache passes run split by set over ``groups`` (``reference/cache.py``).
"""
from __future__ import annotations

import importlib
import math

import numpy as np

from reference import cache, graphs, prefetchers, scoring

LIMITS = {"trace_mismatch": 0, "hit_mismatch": 0, "row_gap": 0, "stream_mismatch": 0}
ROW_FIELDS = (
    "speedup",
    "coverage",
    "accuracy",
    "ipc_baseline",
    "ipc_prefetch",
    "issued",
    "useful",
    "late",
    "evicted_early",
    "overpredicted",
    "redundant",
    "baseline_l2_misses",
    "extra_traffic",
    "metadata_traffic",
    "dram_demand",
    "dram_total",
)
# What a sharded job's rows say of its demand misses in the scored window:
# L2 misses beside next-line alone, and DRAM demand beside the prefetcher.
DEMAND_COUNTS = ("baseline_l2_misses", "dram_demand")


def _differ(a: np.ndarray, b: np.ndarray) -> int:
    n = min(len(a), len(b))
    return int(np.count_nonzero(np.asarray(a[:n]) != np.asarray(b[:n]))) + abs(
        len(a) - len(b)
    )


def _gap(got: float, want: float) -> float:
    if got == want:
        return 0.0
    if not (math.isfinite(got) and math.isfinite(want)):
        return math.inf
    return abs(got - want) / max(abs(want), 1e-12)


class Reference:
    """The configuration's plain reference, with its base graph made once."""

    def __init__(
        self, config: dict, policy: str = "lru", groups: cache.SetGroups = cache.SERIAL
    ):
        self.config = config
        self.policy = policy
        self.groups = groups
        self.workload = importlib.import_module(f"reference.{config['reference']}")
        self._graph = None
        self._last = None  # (seed, trace, demand, streams) of the last workload

    @property
    def graph(self) -> graphs.Graph:
        if self._graph is None:
            self._graph = graphs.make_graph(self.config["graph"])
        return self._graph

    def simulate(self, seed: int) -> tuple:
        """The workload's trace and its demand masks (the last one kept,
        since a job sweep scores one workload many times)."""
        if self._last is None or self._last[0] != seed:
            self._last = None
            trace = self.workload.trace(self.config, seed, self.graph)
            d = scoring.demand(
                trace["blocks"], self.config["hierarchy"], self.policy, self.groups
            )
            self._last = (seed, trace, d, {})
        return self._last[1], self._last[2]

    def row(self, d: scoring.Demand, stream: tuple, eval_from: int) -> dict:
        h, tm = self.config["hierarchy"], self.config["timing"]
        return scoring.score(d, stream, eval_from, h, tm, self.groups)

    def stream(self, seed: int, entry: dict) -> tuple:
        """The plain generator's stream of a prefetcher entry on the
        workload, from this reference's own trace and demand."""
        trace, d = self.simulate(seed)
        streams = self._last[3]
        key = (entry["registry"], tuple(sorted(entry.get("overrides", {}).items())))
        if key not in streams:
            base = scoring.baseline(d, self.config["hierarchy"], self.groups)
            streams[key] = prefetchers.generate(entry, prefetchers.Inputs.of(trace, d, base))
        return streams[key]


def compare(jobs: list, ref: Reference, truth: Reference | None = None) -> dict:
    """The four numbers over ``jobs``.

    Each job is a dict with ``workloads`` (per workload: ``seed``,
    ``block``, ``iter_id``, ``eval_from``, ``l1_hit``, ``l2_hit``,
    ``llc_hit`` and ``rows``, a list of ``(row, stream, entry)``: the row,
    the stream the prefetcher issued, and its entry of the configuration or
    traffic; a sharded job's workload has no ``*_hit`` masks).  With
    ``truth`` given, ``ref`` stands in for the timed path (the control): its
    answers and its own generators' streams replace the program's and
    ``truth`` judges them, in the same form as the job's.
    """
    out = {"trace_mismatch": 0, "hit_mismatch": 0, "row_gap": 0.0, "stream_mismatch": 0}
    judge = truth or ref
    for job in jobs:
        for w in job["workloads"]:
            want, d = judge.simulate(w["seed"])
            entries = [entry for _, _, entry in w["rows"]]
            if truth is None:
                got = dict(w, rows=[(row, stream) for row, stream, _ in w["rows"]])
            else:
                got = _answers(ref, w["seed"], entries)
            out["trace_mismatch"] += (
                _differ(got["block"], want["blocks"])
                + _differ(got["iter_id"], want["iter_id"])
                + abs(int(got["eval_from"]) - int(want["eval_from"]))
            )
            masks = "l1_hit" in w
            if masks:
                for level in ("l1_hit", "l2_hit", "llc_hit"):
                    out["hit_mismatch"] += _differ(got[level], getattr(d, level))
            for (row, stream), entry in zip(got["rows"], entries):
                out["stream_mismatch"] += stream_mismatch(
                    stream, judge.stream(w["seed"], entry),
                    prefetchers.GENERATORS[entry["registry"]].ordered,
                )
                expect = judge.row(d, stream, int(want["eval_from"]))
                if not masks:
                    for field in DEMAND_COUNTS:
                        out["hit_mismatch"] += abs(int(row[field]) - int(expect[field]))
                for field in ROW_FIELDS:
                    out["row_gap"] = max(
                        out["row_gap"], _gap(float(row[field]), float(expect[field]))
                    )
    return out


def stream_mismatch(got: tuple, want: tuple, ordered: bool = True) -> int:
    """Prefetches of ``got`` that differ from ``want``, each a ``(blocks,
    pos, metadata_bytes)``, in issue order (see the module's docstring)."""
    (gb, gp), (wb, wp) = _issue_order(*got[:2], ordered), _issue_order(*want[:2], ordered)
    n = min(len(gb), len(wb))
    return (
        int(np.count_nonzero((gb[:n] != wb[:n]) | (gp[:n] != wp[:n])))
        + abs(len(gb) - len(wb))
        + int(int(got[2]) != int(want[2]))
    )


def _issue_order(blocks, pos, ordered: bool) -> tuple:
    blocks = np.asarray(blocks, dtype=np.int64)
    pos = np.asarray(pos, dtype=np.int64)
    order = np.argsort(pos, kind="stable") if ordered else np.lexsort((blocks, pos))
    return blocks[order], pos[order]


def _answers(ref: Reference, seed: int, entries: list) -> dict:
    """What ``ref`` itself answers for one workload, shaped like a job's."""
    trace, d = ref.simulate(seed)
    streams = [ref.stream(seed, entry) for entry in entries]
    return dict(
        block=trace["blocks"],
        iter_id=trace["iter_id"],
        eval_from=trace["eval_from"],
        l1_hit=d.l1_hit,
        l2_hit=d.l2_hit,
        llc_hit=d.llc_hit,
        rows=[(ref.row(d, s, trace["eval_from"]), s) for s in streams],
    )


def verdict(numbers: dict) -> bool:
    return all(numbers[k] <= limit for k, limit in LIMITS.items())
