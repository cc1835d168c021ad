"""Plain scoring of a prefetch stream against a demand trace.

Paper setup (§VII): the baseline system runs a next-line L2 prefetcher
(issuer 0); an evaluated prefetcher (issuer 1) runs beside it.  Demand L2
events sit at doubled positions ``2p`` and prefetches at ``2p + 1``, so a
prefetch issued at access ``p`` comes after the demand at ``p``.  The
timing arithmetic is the simulator's calibrated miss-penalty model, stated
in the configuration's ``timing`` entry.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from reference import cache

BLOCK_BITS = 6


@dataclasses.dataclass
class Demand:
    """A trace with its demand hit masks, cascaded L1 -> L2 -> LLC."""

    blocks: np.ndarray
    l1_hit: np.ndarray
    l2_pos: np.ndarray  # positions of L1 misses (the L2 accesses)
    l2_hit: np.ndarray
    llc_hit: np.ndarray  # over the L2 misses
    policy: str = "lru"
    nextline_outcome: dict | None = None  # the baseline run, made once

    @property
    def l2_blocks(self) -> np.ndarray:
        return self.blocks[self.l2_pos]

    @property
    def l2_miss_pos(self) -> np.ndarray:
        return self.l2_pos[~self.l2_hit]


def demand(
    blocks: np.ndarray,
    hierarchy: dict,
    policy: str = "lru",
    groups: cache.SetGroups = cache.SERIAL,
) -> Demand:
    levels = [_geometry(hierarchy[k]) for k in ("l1", "l2", "llc")]
    l1, l2, llc = cache.hierarchy(blocks, levels, policy, groups)
    return Demand(blocks, l1, np.flatnonzero(~l1).astype(np.int64), l2, llc, policy)


def _geometry(level: dict) -> tuple:
    lines = level["size_bytes"] >> BLOCK_BITS
    return lines // level["ways"], level["ways"]


def nextline(d: Demand) -> tuple:
    """Degree-1 next-line on L2 accesses, repeated lines filtered."""
    b = d.l2_blocks
    keep = np.ones(len(b), dtype=bool)
    keep[1:] = b[1:] != b[:-1]
    return b[keep] + 1, d.l2_pos[keep]


def outcome(
    d: Demand,
    pf_blocks: np.ndarray,
    pf_pos: np.ndarray,
    pf_issuer: np.ndarray,
    hierarchy: dict,
    policy: str = "lru",
    groups: cache.SetGroups = cache.SERIAL,
) -> dict:
    """Demand and prefetch events of one run with prefetching, per event."""
    order = np.argsort(pf_pos, kind="stable")
    pf_blocks, pf_pos, pf_issuer = pf_blocks[order], pf_pos[order], pf_issuer[order]
    nd, npf = len(d.l2_pos), len(pf_blocks)
    pos2 = np.concatenate([2 * d.l2_pos, 2 * pf_pos + 1])
    merged = np.argsort(pos2, kind="stable")
    blocks = np.concatenate([d.l2_blocks, pf_blocks])[merged]
    is_pf = np.concatenate([np.zeros(nd, bool), np.ones(npf, bool)])[merged]
    issuer = np.concatenate([np.full(nd, -1, np.int8), pf_issuer.astype(np.int8)])
    issuer = issuer[merged]
    pos2 = pos2[merged]
    l2_sets, l2_ways = _geometry(hierarchy["l2"])
    l2 = groups.run(
        cache.prefetch_pass,
        l2_sets,
        blocks,
        is_pf,
        pos2,
        issuer,
        args=(l2_ways, 2 * hierarchy["pf_fill_window"], policy),
    )
    miss = ~l2["hit"]
    llc_sets, llc_ways = _geometry(hierarchy["llc"])
    llc_hit = groups.run(cache.hits, llc_sets, blocks[miss], args=(llc_ways, policy))
    llc_is_pf = is_pf[miss]
    dem = ~is_pf
    # Prefetches whose block no later baseline L2 miss demands: the last
    # miss position of each block (positions ascend), looked up per prefetch.
    # A block that never misses reads -1.
    miss_blocks = d.l2_blocks[~d.l2_hit]
    known, last = np.unique(miss_blocks[::-1], return_index=True)
    last_pos = np.append(d.l2_miss_pos[len(miss_blocks) - 1 - last], -1)
    at = np.searchsorted(known, pf_blocks)
    inside = at < len(known)
    inside[inside] = known[at[inside]] == pf_blocks[inside]
    no_future = last_pos[np.where(inside, at, len(known))] <= pf_pos
    pf_sel = is_pf
    return dict(
        pf_pos=pf_pos,
        pf_issuer=pf_issuer,
        pf_redundant=l2["redundant"][pf_sel],
        pf_early=l2["early"][pf_sel],
        pf_no_future=no_future,
        pf_llc_in_dram=~llc_hit[llc_is_pf],
        pf_llc_in_pos=(pos2[miss] // 2)[llc_is_pf],
        demand_l2_hit=l2["hit"][dem],
        demand_useful=l2["useful"][dem],
        demand_late=l2["late"][dem],
        demand_fill_issuer=l2["fill_issuer"][dem],
        demand_llc_hit=llc_hit[~llc_is_pf],
    )


def baseline(d: Demand, hierarchy: dict, groups: cache.SetGroups = cache.SERIAL) -> dict:
    """The baseline run, next-line beside demand, made once per ``d``."""
    if d.nextline_outcome is None:
        nl_blocks, nl_pos = nextline(d)
        d.nextline_outcome = outcome(
            d,
            nl_blocks,
            nl_pos,
            np.zeros(len(nl_blocks), np.int8),
            hierarchy,
            d.policy,
            groups,
        )
    return d.nextline_outcome


def _mlp(miss_pos: np.ndarray, window: int, cap: float) -> float:
    if len(miss_pos) < 2:
        return 1.0
    pos = np.sort(miss_pos)
    sample = pos[:: max(len(pos) // 1_000_000, 1)]
    hi = np.searchsorted(pos, sample + window, side="right")
    lo = np.searchsorted(pos, sample, side="left")
    return float(np.clip((hi - lo).mean(), 1.0, cap))


def _baseline_counts(d: Demand, t0: int) -> dict:
    in_l2 = d.l2_pos >= t0
    miss_pos = d.l2_miss_pos
    return dict(
        accesses=len(d.blocks) - t0,
        l1_miss=int(in_l2.sum()),
        l2_miss=int((~d.l2_hit & in_l2).sum()),
        dram=int((~d.llc_hit & (miss_pos >= t0)).sum()),
    )


def _late_cost(d: Demand, t0: int, h: dict, tm: dict) -> float:
    base = _baseline_counts(d, t0)
    if base["l2_miss"] <= 0:
        return 0.0
    mp = d.l2_miss_pos
    dp = mp[~d.llc_hit]
    mlp_llc = _mlp(mp[mp >= t0], tm["mlp_window"], tm["mlp_cap_llc"])
    mlp_dram = _mlp(dp[dp >= t0], tm["mlp_window"], tm["mlp_cap_dram"])
    llc_hits = max(base["l2_miss"] - base["dram"], 0)
    total = (
        h["llc"]["latency"] * llc_hits / mlp_llc
        + h["dram_latency"] * base["dram"] / mlp_dram
    )
    return total / base["l2_miss"]


def _cycles(d: Demand, o: dict, t0: int, h: dict, tm: dict, meta_lines: int):
    base = _baseline_counts(d, t0)
    miss_pos = d.l2_pos[~o["demand_l2_hit"]]
    in_win = miss_pos >= t0
    dram_flags = ~o["demand_llc_hit"]
    l2_misses = int(in_win.sum())
    dram_demand = int((dram_flags & in_win).sum())
    pf_dram = int((o["pf_llc_in_dram"] & (o["pf_llc_in_pos"] >= t0)).sum())
    late = int((o["demand_late"] & (d.l2_pos >= t0)).sum())
    dram_total = dram_demand + pf_dram + meta_lines
    dram_pos = miss_pos[dram_flags]
    mlp_llc = _mlp(miss_pos[in_win], tm["mlp_window"], tm["mlp_cap_llc"])
    mlp_dram = _mlp(dram_pos[dram_pos >= t0], tm["mlp_window"], tm["mlp_cap_dram"])
    extra = max(dram_total / max(base["dram"], 1) - 1.0, 0.0)
    dram_eff = h["dram_latency"] * (1.0 + tm["bw_sensitivity"] * extra)
    cycles = (
        tm["cycles_per_access"] * base["accesses"]
        + tm["l2_hit_penalty"] * base["l1_miss"]
        + h["llc"]["latency"] * max(l2_misses - dram_demand, 0) / mlp_llc
        + dram_eff * dram_demand / mlp_dram
        + tm["late_fraction"] * _late_cost(d, t0, h, tm) * late
    )
    return cycles, dict(l2_misses=l2_misses, dram_demand=dram_demand, dram_total=dram_total)


def score(
    d: Demand,
    stream: tuple,
    t0: int,
    hierarchy: dict,
    timing: dict,
    groups: cache.SetGroups = cache.SERIAL,
) -> dict:
    """The row of one evaluated prefetcher: ``stream`` is its
    ``(blocks, pos, metadata_bytes)``, scored beside next-line from ``t0``
    under the replacement policy ``d`` was simulated with."""
    policy = d.policy
    nl_blocks, nl_pos = nextline(d)
    base_o = baseline(d, hierarchy, groups)
    x_blocks, x_pos, meta_bytes = stream
    x_blocks = np.asarray(x_blocks, dtype=np.int64)
    x_pos = np.asarray(x_pos, dtype=np.int64)
    o = outcome(
        d,
        np.concatenate([nl_blocks, x_blocks]),
        np.concatenate([nl_pos, x_pos]),
        np.concatenate([np.zeros(len(nl_blocks), np.int8), np.ones(len(x_blocks), np.int8)]),
        hierarchy,
        policy,
        groups,
    )
    base = _baseline_counts(d, t0)
    base_cycles, base_counts = _cycles(d, base_o, t0, hierarchy, timing, 0)
    meta_lines = int(meta_bytes) >> BLOCK_BITS
    run_cycles, run_counts = _cycles(d, o, t0, hierarchy, timing, meta_lines)
    sel_pf = (o["pf_pos"] >= t0) & (o["pf_issuer"] == 1)
    useful_mask = o["demand_useful"] & (d.l2_pos >= t0) & (o["demand_fill_issuer"] == 1)
    useful = int(useful_mask.sum())
    issued = int(sel_pf.sum())
    redundant = int((o["pf_redundant"] & sel_pf).sum())
    dram_b = base_counts["dram_total"]
    return dict(
        accuracy=useful / max(issued - redundant, 1),
        coverage=useful / max(base_counts["l2_misses"], 1),
        speedup=base_cycles / max(run_cycles, 1e-9),
        ipc_baseline=base["accesses"] / max(base_cycles, 1e-9),
        ipc_prefetch=base["accesses"] / max(run_cycles, 1e-9),
        issued=issued,
        useful=useful,
        late=int((o["demand_late"] & useful_mask).sum()),
        evicted_early=int((o["pf_early"] & sel_pf).sum()),
        overpredicted=int((o["pf_no_future"] & sel_pf).sum()),
        redundant=redundant,
        baseline_l2_misses=base_counts["l2_misses"],
        extra_traffic=(run_counts["dram_total"] - dram_b) / max(dram_b, 1),
        metadata_traffic=meta_lines / max(dram_b, 1),
        dram_demand=run_counts["dram_demand"],
        dram_total=run_counts["dram_total"],
    )
