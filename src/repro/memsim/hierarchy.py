"""Hierarchy orchestration: shared demand profile + per-prefetcher runs.

Logical time convention: every event carries a position on the *full* access
trace; merged demand/prefetch ordering doubles positions so a prefetch
triggered by access ``p`` lands at ``2p+1`` — after its trigger, before the
next demand access at ``2(p+1)``.

All per-event output arrays are kept so metrics can be evaluated over a
position window (``eval_from_pos``): the paper evaluates BFS/BellmanFord on
the *second* (post-graph-change) run only, with caches warm from run 1.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.memsim import classify_device
from repro.memsim.config import HierarchyConfig
from repro.memsim.engine import (
    CacheState,
    cache_pass,
    cache_pass_batch,
    current_engine,
    init_state,
)
from repro.memsim.fused import fused_cache_pass, fused_cache_pass_batch
from repro.memsim.scan_cache import classify_prefetch_events


def _stage(name: str):
    """Per-level stage-timer hook (``cache_pass[l1|l2|llc|fused]``).

    Imported lazily: :mod:`repro.core.exec.timers` is dependency-free, but
    reaching it imports the ``repro.core`` package, which imports this
    module back — fine at call time, a cycle at import time.
    """
    from repro.core.exec.timers import stage

    return stage(name)


def _span(name: str):
    """A span of the scoring pass (``prefetch.merge``/``prefetch.classify``),
    imported lazily for the same reason as :func:`_stage`."""
    from repro.core.obs.spans import span

    return span(name)


def _count_launch(batched: int = 0) -> None:
    """Metrics counters for fused-pass dispatches (no-op when obs is off):
    ``fused.launches`` counts scan launches, ``fused.batched_streams`` the
    streams a batched launch covered — together they make the
    three-passes→one-launch collapse visible in the telemetry snapshot."""
    from repro.core.obs.spans import inc

    inc("fused.launches")
    if batched:
        inc("fused.batched_streams", batched)


def _demand_levels(cfg: HierarchyConfig):
    return (
        (cfg.l1.sets, cfg.l1.ways),
        (cfg.l2.sets, cfg.l2.ways),
        (cfg.llc.sets, cfg.llc.ways),
    )


@dataclasses.dataclass
class DemandProfile:
    """Baseline (no-prefetch) simulation of one full trace."""

    blocks: np.ndarray  # full trace line ids
    iter_id: np.ndarray  # full trace iteration (epoch) ids
    l1_hit: np.ndarray  # (N,) bool
    # L1-miss substream (these are the L2 accesses):
    l2_pos: np.ndarray  # positions into the full trace
    l2_blocks: np.ndarray
    l2_iter: np.ndarray
    l2_hit: np.ndarray  # baseline L2 hit mask over substream
    llc_hit: np.ndarray  # baseline LLC hit mask over the L2-miss substream
    cfg: HierarchyConfig

    @property
    def num_accesses(self) -> int:
        return len(self.blocks)

    @property
    def l2_miss_pos(self) -> np.ndarray:
        return self.l2_pos[~self.l2_hit]

    @property
    def l2_miss_blocks(self) -> np.ndarray:
        return self.l2_blocks[~self.l2_hit]

    @property
    def l2_miss_iter(self) -> np.ndarray:
        return self.l2_iter[~self.l2_hit]

    def baseline_counts(self, from_pos: int = 0) -> dict:
        # l2_pos / l2_miss_pos are sorted, so window counts are searchsorteds.
        i_l2 = int(np.searchsorted(self.l2_pos, from_pos))
        mp = self.l2_miss_pos
        i_llc = int(np.searchsorted(mp, from_pos))
        dram = int((~self.llc_hit[i_llc:]).sum())
        return dict(
            accesses=self.num_accesses - from_pos,
            l1_miss=len(self.l2_pos) - i_l2,
            l2_miss=int((~self.l2_hit[i_l2:]).sum()),
            llc_miss=dram,
            dram=dram,
        )


@dataclasses.dataclass
class DemandState:
    """Carried hierarchy state for chunked (sharded) demand simulation.

    Bundles the canonical per-level :class:`CacheState` carries plus the
    global position of the next access, so a sequence of
    :func:`simulate_demand` calls over trace chunks produces profiles whose
    concatenation is bit-identical to one whole-trace call — the shard-seam
    contract the streaming scorer builds on.
    """

    l1: CacheState
    l2: CacheState
    llc: CacheState
    pos_offset: int = 0


def demand_init_state(cfg: HierarchyConfig) -> DemandState:
    """Cold-cache carry (equivalent to passing ``state=None``)."""
    return DemandState(
        l1=init_state(cfg.l1.sets, cfg.l1.ways),
        l2=init_state(cfg.l2.sets, cfg.l2.ways),
        llc=init_state(cfg.llc.sets, cfg.llc.ways),
        pos_offset=0,
    )


def simulate_demand(
    blocks: np.ndarray,
    iter_id: np.ndarray,
    cfg: HierarchyConfig,
    state: DemandState | None = None,
    return_state: bool = False,
):
    """Baseline demand simulation; optionally resuming from / yielding a
    :class:`DemandState` carry for chunked traces.  With a carry, ``l2_pos``
    is expressed in *global* trace positions (``state.pos_offset`` +
    chunk-local index), keeping windowed metrics chunk-invariant."""
    offset = 0
    if state is not None:
        offset = state.pos_offset
    if current_engine() == "fused":
        return _simulate_demand_fused(blocks, iter_id, cfg, state, return_state)
    with _stage("cache_pass[l1]"):
        l1_hit = cache_pass(
            blocks,
            cfg.l1.sets,
            cfg.l1.ways,
            state=state.l1 if state is not None else None,
            return_state=return_state,
        )
        if return_state:
            l1_hit, l1_state = l1_hit
    l2_pos = np.flatnonzero(~l1_hit).astype(np.int64) + offset
    l2_blocks = blocks[l2_pos - offset]
    l2_iter = iter_id[l2_pos - offset]
    with _stage("cache_pass[l2]"):
        l2_hit = cache_pass(
            l2_blocks,
            cfg.l2.sets,
            cfg.l2.ways,
            state=state.l2 if state is not None else None,
            return_state=return_state,
        )
        if return_state:
            l2_hit, l2_state = l2_hit
    llc_in = l2_blocks[~l2_hit]
    with _stage("cache_pass[llc]"):
        llc_hit = cache_pass(
            llc_in,
            cfg.llc.sets,
            cfg.llc.ways,
            state=state.llc if state is not None else None,
            return_state=return_state,
        )
        if return_state:
            llc_hit, llc_state = llc_hit
    profile = DemandProfile(
        blocks=blocks,
        iter_id=iter_id,
        l1_hit=l1_hit,
        l2_pos=l2_pos,
        l2_blocks=l2_blocks,
        l2_iter=l2_iter,
        l2_hit=l2_hit,
        llc_hit=llc_hit,
        cfg=cfg,
    )
    if not return_state:
        return profile
    next_state = DemandState(
        l1=l1_state, l2=l2_state, llc=llc_state, pos_offset=offset + len(blocks)
    )
    return profile, next_state


def _profile_from_levels(
    blocks: np.ndarray,
    iter_id: np.ndarray,
    cfg: HierarchyConfig,
    lvl: np.ndarray,
    offset: int,
) -> DemandProfile:
    """Unpack a fused pass's hit-level array (0=L1 hit, 1=L2, 2=LLC,
    3=DRAM) into the cascaded per-level masks of :class:`DemandProfile` —
    each level's mask covers exactly the miss substream of the level
    above, identical to the per-level path by set independence."""
    l1_hit = lvl == 0
    l2_pos = np.flatnonzero(~l1_hit).astype(np.int64) + offset
    l2_lvl = lvl[~l1_hit]
    l2_hit = l2_lvl == 1
    return DemandProfile(
        blocks=blocks,
        iter_id=iter_id,
        l1_hit=l1_hit,
        l2_pos=l2_pos,
        l2_blocks=blocks[l2_pos - offset],
        l2_iter=iter_id[l2_pos - offset],
        l2_hit=l2_hit,
        llc_hit=l2_lvl[~l2_hit] == 2,
        cfg=cfg,
    )


def _simulate_demand_fused(
    blocks: np.ndarray,
    iter_id: np.ndarray,
    cfg: HierarchyConfig,
    state: DemandState | None,
    return_state: bool,
):
    """One carried L1→L2→LLC scan instead of three passes with host-side
    miss compaction between them (the ``fused`` engine's demand path)."""
    offset = state.pos_offset if state is not None else 0
    states = [state.l1, state.l2, state.llc] if state is not None else None
    with _stage("cache_pass[fused]"):
        res = fused_cache_pass(
            blocks, _demand_levels(cfg), states, return_states=return_state
        )
        _count_launch()
    lvl = res[0] if return_state else res
    profile = _profile_from_levels(blocks, iter_id, cfg, lvl, offset)
    if not return_state:
        return profile
    l1_state, l2_state, llc_state = res[1]
    return profile, DemandState(
        l1=l1_state, l2=l2_state, llc=llc_state, pos_offset=offset + len(blocks)
    )


def simulate_demand_batch(
    items: list,
    cfg: HierarchyConfig,
) -> list:
    """Demand-simulate same-hierarchy traces as one batched dispatch.

    ``items`` is a list of ``(blocks, iter_id)`` pairs (e.g. the seed
    replicas of one bench cell).  Under the ``fused`` engine the traces
    pad to a common bucket and run as a single vmapped scan when the
    cost-based plan chooser picks the carried scan for every member
    (run-collapse shrank each bucket); otherwise they loop through the
    bit-identical per-stream plan.  Other engines loop
    :func:`simulate_demand`.  Results are bit-identical either way.
    """
    if current_engine() != "fused":
        return [simulate_demand(b, it, cfg) for b, it in items]
    with _stage("cache_pass[fused]"):
        lvls = fused_cache_pass_batch(
            [b for b, _ in items], _demand_levels(cfg)
        )
        _count_launch(batched=len(items))
    return [
        _profile_from_levels(b, it, cfg, lvl, 0)
        for (b, it), lvl in zip(items, lvls)
    ]


@dataclasses.dataclass
class PrefetchOutcome:
    """Per-prefetcher simulation result over one trace (per-event arrays)."""

    pf_pos: np.ndarray  # issue positions (full-trace units)
    pf_issuer: np.ndarray  # (n_pf,) int8 issuer id (composite prefetching)
    pf_redundant: np.ndarray  # (n_pf,) bool: block already resident
    pf_no_future: np.ndarray  # (n_pf,) bool: never demanded after issue
    pf_llc_in_dram: np.ndarray  # over pf L2-misses: went to DRAM
    pf_llc_in_pos: np.ndarray  # their positions
    demand_l2_hit: np.ndarray  # (n_demand,) with prefetcher
    demand_useful: np.ndarray  # (n_demand,) demand hit on pf line
    demand_late: np.ndarray  # (n_demand,) useful but still in flight
    demand_fill_issuer: np.ndarray  # (n_demand,) issuer of the useful fill, -1
    demand_llc_hit: np.ndarray  # over demand L2 misses (with prefetcher)
    evicted_early_total: int
    pf_early: np.ndarray  # (n_pf,) prefetch fill evicted before reuse
    metadata_bytes: int = 0
    # LLC-input stream (only with ``keep_llc_stream=True``): the exact
    # event sequence the private LLC pass consumed, in simulation order —
    # block ids, doubled positions (2p demand / 2p+1 prefetch), and the
    # is-prefetch flag.  The multi-tenant serving layer re-plays these
    # events through one *shared* LLC (repro.memsim.shared_llc) and patches
    # ``demand_llc_hit``/``pf_llc_in_dram`` with the contended hit masks.
    # Default None keeps artifact round-trips and pickling unchanged.
    llc_in_blocks: np.ndarray | None = None
    llc_in_pos2: np.ndarray | None = None
    llc_in_is_pf: np.ndarray | None = None

    @property
    def issued(self) -> int:
        return len(self.pf_pos)


def simulate_with_prefetch(
    profile: DemandProfile,
    pf_blocks: np.ndarray,
    pf_pos: np.ndarray,
    pf_issuer: np.ndarray | None = None,
    metadata_bytes: int = 0,
    keep_llc_stream: bool = False,
) -> PrefetchOutcome:
    """Re-simulate L2+LLC with a (possibly multi-issuer) prefetch stream.

    ``keep_llc_stream=True`` additionally stashes the LLC-input event
    stream (blocks, doubled positions, is-prefetch flags) on the outcome
    so a shared-LLC pass can re-simulate it under multi-tenant contention.
    """
    cfg = profile.cfg
    nd = len(profile.l2_blocks)
    npf = len(pf_blocks)
    if npf == 0:
        d_miss = ~profile.l2_hit
        return PrefetchOutcome(
            pf_pos=np.zeros(0, dtype=np.int64),
            pf_issuer=np.zeros(0, dtype=np.int8),
            pf_redundant=np.zeros(0, dtype=bool),
            pf_no_future=np.zeros(0, dtype=bool),
            pf_llc_in_dram=np.zeros(0, dtype=bool),
            pf_llc_in_pos=np.zeros(0, dtype=np.int64),
            demand_l2_hit=profile.l2_hit.copy(),
            demand_useful=np.zeros(nd, dtype=bool),
            demand_late=np.zeros(nd, dtype=bool),
            demand_fill_issuer=np.full(nd, -1, dtype=np.int8),
            demand_llc_hit=profile.llc_hit.copy(),
            evicted_early_total=0,
            pf_early=np.zeros(0, dtype=bool),
            metadata_bytes=metadata_bytes,
            llc_in_blocks=profile.l2_blocks[d_miss] if keep_llc_stream else None,
            llc_in_pos2=2 * profile.l2_pos[d_miss] if keep_llc_stream else None,
            llc_in_is_pf=np.zeros(int(d_miss.sum()), dtype=bool)
            if keep_llc_stream
            else None,
        )

    with _span("prefetch.merge"):
        merged = _merge_prefetch_stream(profile, pf_blocks, pf_pos, pf_issuer)
    mblocks_s = merged["mblocks_s"]
    # Scoring a single stream runs the per-level cascade under every
    # engine: the L2 substream has no L1-filterable runs to collapse, so
    # a carried L2→LLC scan would add gather/scatter cost per step
    # without removing any.  The fused engine's scoring win is *batching*
    # — see simulate_with_prefetch_batch.
    with _stage("cache_pass[l2]"):
        hit = cache_pass(mblocks_s, cfg.l2.sets, cfg.l2.ways)
    # LLC sees every L2 miss (demand or prefetch) in order.
    with _stage("cache_pass[llc]"):
        llc_hit = cache_pass(
            mblocks_s[~hit], cfg.llc.sets, cfg.llc.ways
        )
    with _span("prefetch.classify"):
        return _finish_prefetch_outcome(
            profile, merged, hit, llc_hit, metadata_bytes, keep_llc_stream
        )


def simulate_with_prefetch_batch(
    profile: DemandProfile,
    streams: list,
    metadata_bytes: list | None = None,
    keep_llc_stream: bool = False,
) -> list:
    """Score several prefetch streams against one profile in one dispatch.

    ``streams`` is a list of ``(pf_blocks, pf_pos, pf_issuer)`` triples
    (``pf_issuer`` may be None) — typically one per prefetcher family of a
    workload.  Under the ``fused`` engine the merged L2 streams pad to a
    common bucket and run as one vmapped set-parallel launch per level
    (:func:`repro.memsim.engine.cache_pass_batch`) — the family's
    ``2 × n_prefetchers`` scoring launches collapse to two; other engines
    (and empty streams) loop :func:`simulate_with_prefetch`.  Outcomes
    are bit-identical to the loop either way.
    """
    meta = metadata_bytes if metadata_bytes is not None else [0] * len(streams)
    if current_engine() != "fused" or any(len(s[0]) == 0 for s in streams):
        return [
            simulate_with_prefetch(
                profile, b, p, issuer, m, keep_llc_stream=keep_llc_stream
            )
            for (b, p, issuer), m in zip(streams, meta)
        ]
    cfg = profile.cfg
    with _span("prefetch.merge"):
        merged = [
            _merge_prefetch_stream(profile, b, p, issuer)
            for b, p, issuer in streams
        ]
    with _stage("cache_pass[l2]"):
        l2_hits = cache_pass_batch(
            [m["mblocks_s"] for m in merged], cfg.l2.sets, cfg.l2.ways
        )
        _count_launch(batched=len(streams))
    with _stage("cache_pass[llc]"):
        llc_hits = cache_pass_batch(
            [m["mblocks_s"][~h] for m, h in zip(merged, l2_hits)],
            cfg.llc.sets,
            cfg.llc.ways,
        )
        _count_launch(batched=len(streams))
    with _span("prefetch.classify"):
        return [
            _finish_prefetch_outcome(profile, m, h, lh, mb, keep_llc_stream)
            for m, h, lh, mb in zip(merged, l2_hits, llc_hits, meta)
        ]


def _merge_prefetch_stream(
    profile: DemandProfile,
    pf_blocks: np.ndarray,
    pf_pos: np.ndarray,
    pf_issuer: np.ndarray | None,
) -> dict:
    """Interleave a prefetch stream into the demand L2 substream.

    Demand events land at doubled positions ``2p``, prefetches at
    ``2p+1``.  Both substreams are position-sorted, so the merge is a
    single searchsorted instead of a full argsort of the concatenation.
    """
    nd = len(profile.l2_blocks)
    npf = len(pf_blocks)
    pf_blocks = np.asarray(pf_blocks, dtype=np.int64)
    pf_pos = np.asarray(pf_pos, dtype=np.int64)
    if pf_issuer is None:
        pf_issuer = np.zeros(npf, dtype=np.int8)
    pf_issuer = np.asarray(pf_issuer, dtype=np.int8)
    if npf > 1 and np.any(pf_pos[1:] < pf_pos[:-1]):
        o = np.argsort(pf_pos, kind="stable")
        pf_pos, pf_blocks, pf_issuer = pf_pos[o], pf_blocks[o], pf_issuer[o]

    total = nd + npf
    pf_slots = np.searchsorted(2 * profile.l2_pos, 2 * pf_pos + 1) + np.arange(npf)
    demand_slots = np.ones(total, dtype=bool)
    demand_slots[pf_slots] = False
    demand_slots = np.flatnonzero(demand_slots)
    mpos_s = np.empty(total, dtype=np.int64)
    mblocks_s = np.empty(total, dtype=np.int64)
    m_is_pf_s = np.zeros(total, dtype=bool)
    mpos_s[demand_slots] = 2 * profile.l2_pos
    mpos_s[pf_slots] = 2 * pf_pos + 1
    mblocks_s[demand_slots] = profile.l2_blocks
    mblocks_s[pf_slots] = pf_blocks
    m_is_pf_s[pf_slots] = True

    m_issuer = np.full(total, -1, dtype=np.int8)
    m_issuer[pf_slots] = pf_issuer
    return dict(
        pf_blocks=pf_blocks,
        pf_pos=pf_pos,
        pf_issuer=pf_issuer,
        pf_slots=pf_slots,
        demand_slots=demand_slots,
        mpos_s=mpos_s,
        mblocks_s=mblocks_s,
        m_is_pf_s=m_is_pf_s,
        m_issuer=m_issuer,
    )


def _classify_on_device() -> bool:
    """Classification runs as the jitted chain program on a TPU (the
    host idles the chip otherwise) and in numpy elsewhere, where XLA:CPU
    is slower than numpy; the ``reference`` engine keeps the host oracle."""
    return jax.default_backend() == "tpu" and current_engine() != "reference"


def _finish_prefetch_outcome(
    profile: DemandProfile,
    merged: dict,
    hit: np.ndarray,
    llc_hit: np.ndarray,
    metadata_bytes: int,
    keep_llc_stream: bool,
) -> PrefetchOutcome:
    """Classify + unmerge one scored stream back into a
    :class:`PrefetchOutcome` (``hit`` over the merged stream, ``llc_hit``
    over its L2-miss substream — however the passes were dispatched)."""
    l2 = _classify_l2(profile, merged, hit)

    # The LLC stream is the merged stream's L2 misses in order; its demand
    # events appear in merged order == pos order == demand-substream order,
    # and its prefetches in prefetch order.
    llc_sel = ~hit
    llc_is_pf = merged["m_is_pf_s"][llc_sel]
    return PrefetchOutcome(
        pf_pos=merged["pf_pos"],
        pf_issuer=merged["pf_issuer"],
        pf_redundant=l2.pf_redundant,
        pf_no_future=l2.pf_no_future,
        pf_llc_in_dram=(~llc_hit)[llc_is_pf],
        pf_llc_in_pos=merged["pf_pos"][~l2.pf_l2_hit],
        demand_l2_hit=l2.demand_l2_hit,
        demand_useful=l2.demand_useful,
        demand_late=l2.demand_late,
        demand_fill_issuer=l2.demand_fill_issuer,
        demand_llc_hit=llc_hit[~llc_is_pf],
        evicted_early_total=int(l2.pf_early.sum()),
        pf_early=l2.pf_early,
        metadata_bytes=metadata_bytes,
        llc_in_blocks=merged["mblocks_s"][llc_sel] if keep_llc_stream else None,
        llc_in_pos2=merged["mpos_s"][llc_sel] if keep_llc_stream else None,
        llc_in_is_pf=llc_is_pf if keep_llc_stream else None,
    )


def _classify_l2(
    profile: DemandProfile, merged: dict, hit: np.ndarray
) -> classify_device.L2Outcome:
    """The L2 classification of one merged stream, on the device where
    :func:`_classify_on_device` says so and the stream fits int32."""
    fill_window = 2 * profile.cfg.pf_fill_window
    mblocks_s, mpos_s = merged["mblocks_s"], merged["mpos_s"]
    if _classify_on_device() and classify_device.fits_int32(
        int(mblocks_s.max()), int(mpos_s[-1]), fill_window
    ):
        from repro.core.obs.spans import inc

        inc("prefetch.classify_device", len(mblocks_s))
        return classify_device.classify_chains(
            mblocks_s,
            mpos_s,
            merged["m_issuer"],
            hit,
            merged["demand_slots"],
            profile.l2_hit,
            fill_window,
        )
    return _classify_l2_on_host(profile, merged, hit, fill_window)


def _classify_l2_on_host(
    profile: DemandProfile, merged: dict, hit: np.ndarray, fill_window: int
) -> classify_device.L2Outcome:
    """The numpy form of :func:`_classify_l2`: the CPU path and the
    oracle of the device program."""
    pf_slots = merged["pf_slots"]
    demand_slots = merged["demand_slots"]
    useful, late, redundant, early, fill_origin = classify_prefetch_events(
        merged["mblocks_s"], merged["m_is_pf_s"], merged["mpos_s"], hit, fill_window
    )
    d_fill = fill_origin[demand_slots]
    return classify_device.L2Outcome(
        demand_l2_hit=hit[demand_slots],
        demand_useful=useful[demand_slots],
        demand_late=late[demand_slots],
        demand_fill_issuer=np.where(
            d_fill >= 0, merged["m_issuer"][np.maximum(d_fill, 0)], -1
        ).astype(np.int8),
        pf_l2_hit=hit[pf_slots],
        pf_redundant=redundant[pf_slots],
        pf_early=early[pf_slots],
        pf_no_future=_no_future_demand(
            merged["pf_blocks"],
            merged["pf_pos"],
            profile.l2_miss_blocks,
            profile.l2_miss_pos,
        ),
    )


def _no_future_demand(
    pf_blocks: np.ndarray,
    pf_pos: np.ndarray,
    demand_blocks: np.ndarray,
    demand_pos: np.ndarray,
) -> np.ndarray:
    """Per-prefetch flag: block never appears in future baseline L2 misses."""
    if len(pf_blocks) == 0:
        return np.zeros(0, dtype=bool)
    if len(demand_blocks) == 0:
        return np.ones(len(pf_blocks), dtype=bool)
    dkey_sort = (demand_blocks.astype(np.int64) << np.int64(31)) | demand_pos
    order = np.argsort(dkey_sort)
    db = demand_blocks[order]
    dp = demand_pos[order]
    BIG = np.int64(1) << 40
    dkey = db.astype(np.int64) * BIG + dp
    pkey = pf_blocks.astype(np.int64) * BIG + pf_pos
    idx = np.searchsorted(dkey, pkey, side="right")
    safe = np.minimum(idx, len(db) - 1)
    has_future = (idx < len(dkey)) & (db[safe] == pf_blocks)
    return ~has_future
