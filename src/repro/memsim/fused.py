"""Fused multi-level hierarchy engine: one carried scan for L1+L2+LLC.

The per-level path (:func:`repro.memsim.hierarchy.simulate_demand`) runs
three separate set-parallel scans with host-side miss-substream compaction
between them — three kernel launches, two device→host→device round trips,
and three padded-matrix builds per trace.  On CPU the per-*step* scan
overhead dominates the per-step compute by orders of magnitude, so a pass
over the full stream costs roughly ``steps × overhead`` regardless of how
much state each step advances.  Fusing all levels into one machine keeps
the step count of the *L1 pass alone* (the full stream grouped at the
smallest set count) while retiring the L2 and LLC launches and both
compaction round trips entirely.

**Group decomposition.** Let ``G = min(sets_l)`` over the fused levels
(set counts are powers of two, so ``G`` divides each).  Group an access
``b`` by ``g = b & (G - 1)``.  Level ``l`` with ``R_l = sets_l / G``
relative sets per group maps ``b`` to set ``s_l = r_l * G + g`` where
``r_l = (b >> log2(G)) & (R_l - 1)`` — every group *exclusively owns*
``R_l`` whole sets at every level, so set independence (the equivalence
behind the set-parallel engine) holds per group for the entire hierarchy
at once.

**Run collapse.** Within one group's substream, a repeat of the
immediately preceding block is a guaranteed L1 hit: the block was just
filled (or refreshed) at that group's L1 set, and — because the group
exclusively owns whole sets at *every* level — nothing between the two
accesses can have touched that set.  :func:`_group_collapse` therefore
keeps only the first access of each run; the dropped repeats are emitted
as hit level 0 at unpack time without ever entering the scan.  The drop
is exact, not approximate: a repeat's only state effect is re-stamping
the MRU line's age, which leaves the per-set age *order* — all that
:func:`canonicalize_state` keeps, and all that LRU consults — unchanged.
Pointer-chasing graph traces are run-heavy (a third of the pgd/comdblp
demand stream), so the collapse typically halves the padded step count
outright.  The collapse is also the fused scan's *cost model*: a fused
step pays an inner-level gather/scatter a cascade step doesn't, so on
the host backend :func:`fused_cache_pass` runs the single scan only when
collapse shrank the pow2 bucket by at least two halvings, and otherwise
takes the bit-identical per-level cascade (short or run-light streams)
on the same fused-select machine.

**Carry layout.** Levels with ``R_l == 1`` ("outer": the group's lanes
are the set) carry dense ``(G, ways)`` tag/age arrays and update via a
fused one-hot select — no gather.  All ``R_l > 1`` levels ("inner") are
merged into a *single* ``(G, sum R_l, 2W)`` array of combined
``[tags | age]`` rows (``W`` = the widest inner ways; pad lanes are never
read), so each step issues exactly **one** gather and **one** scatter for
the whole inner hierarchy — the XLA-CPU cost of a step is dominated by
the number of gather/scatter rows it touches, not by how many levels
those rows advance.

**Bit identity.** The per-level way select is a single fused reduction,
``argmin(where(hitv, INT32_MIN, age))`` over the level's real lanes: at
most one lane can hit (tags are unique within a set), its ``INT32_MIN``
beats every age, and ages are pairwise distinct per set — so the winner
is unique and equals the reference's hit way on a hit and its LRU victim
on a miss, with no tie-break to preserve.
The age stamp is the global step counter: per *set* the stamp order
equals the access order, which is all :func:`canonicalize_state` keeps —
so carried states are bit-identical to the per-level engines', and fused
passes compose with them across shard seams.  A level only observes the
miss substream of the level above (updates are masked by ``alive``),
exactly the compacted substream of the per-level path.

**Batched dispatch.** Same-geometry streams (the per-prefetcher merged
scoring streams of one workload, seed-replica traces of one cell) pad to
a common bucket length and run under one ``vmap`` of the same scan — one
launch for the whole family instead of one per member.
"""
from __future__ import annotations

from functools import lru_cache
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.memsim.engine import (
    CacheState,
    _PAD_FACTOR,
    _PAD_FLOOR_CELLS,
    _bucket_len,
    cache_pass_fused_select,
    canonicalize_state,
    init_state,
)

Geometry = Tuple[Tuple[int, int], ...]  # ((sets, ways), ...) outer→inner

_AGE_PAD = np.iinfo(np.int32).max  # never argmin-selected (defensive: never read)
_TAG_PAD = -2  # never equals a block id >= 0 (defensive: never read)


def fused_group_count(levels: Sequence[Tuple[int, int]]) -> int:
    """G = min(sets): the group granularity of a fused pass."""
    return min(sets for sets, _ in levels)


def state_to_groups(tags_or_age: np.ndarray, groups: int) -> np.ndarray:
    """Reshape a ``(sets, ways)`` level array to ``(groups, R * ways)`` lanes.

    Set ``s = r * groups + g`` lands at ``[g, r * ways + w]``, so lane
    order is ``(relative set, way)`` — the order the kernel's masked
    argmin relies on for reference tie-breaking.
    """
    sets, ways = tags_or_age.shape
    r = sets // groups
    return (
        tags_or_age.reshape(r, groups, ways).transpose(1, 0, 2).reshape(groups, r * ways)
    )


def state_from_groups(lanes: np.ndarray, sets: int, ways: int) -> np.ndarray:
    """Inverse of :func:`state_to_groups`."""
    groups = lanes.shape[0]
    r = sets // groups
    return lanes.reshape(groups, r, ways).transpose(1, 0, 2).reshape(sets, ways)


@lru_cache(maxsize=32)
def _level_split(levels: Geometry):
    """Partition a geometry into outer (``R == 1``) and inner levels.

    Returns ``(inner, W, offs, sum_r)``: ``inner`` is ``(level index,
    R_l, ways)`` triples in level order, ``W`` the widest inner ways,
    ``offs`` each inner level's starting row in the merged carry, and
    ``sum_r`` the merged carry's total row count per group.
    """
    groups = fused_group_count(levels)
    inner = tuple(
        (i, sets // groups, ways)
        for i, (sets, ways) in enumerate(levels)
        if sets > groups
    )
    w_max = max((ways for _, _, ways in inner), default=0)
    offs, o = [], 0
    for _, r, _ in inner:
        offs.append(o)
        o += r
    return inner, w_max, tuple(offs), o


def _group_collapse(blocks: np.ndarray, groups: int):
    """Group the stream and drop run repeats (see *Run collapse* above).

    Returns ``(padded, order, keep, col, row, full_len)``: ``padded`` is
    the ``(max_len, groups)`` matrix of *kept* accesses (column prefixes
    in stream order, ``-1`` tail pads), ``order`` the stable group-by
    sort permutation over the full stream, ``keep`` the first-of-run mask
    over the sorted stream, ``padded[col, row]`` the kept accesses in
    sorted order, and ``full_len`` the bucket the *uncollapsed* stream
    would have padded to (the plan chooser compares the two buckets).
    Unpack per-access results with::

        sorted_res[keep] = res[col, row]   # dropped repeats: L1 hit (0)
        out[order] = sorted_res
    """
    blocks = np.asarray(blocks)
    # Same int32 guard as group_by_set: an id >= 2**31 would wrap negative
    # and alias the -1 pad sentinel.
    assert blocks.max(initial=0) < 2**31, "block ids must fit in int32"
    assert groups <= 1 << 16, "group index must fit the uint16 radix key"
    b32 = blocks.astype(np.int32)
    s = b32 & np.int32(groups - 1)
    # uint16 key → numpy's O(N) radix argsort (same permutation as the
    # int32 timsort path, ~4x faster); see group_by_set.
    order = np.argsort(s.astype(np.uint16), kind="stable")
    bs = b32[order]
    ss = s[order]
    keep = np.ones(len(b32), dtype=bool)
    if len(b32) > 1:
        keep[1:] = (bs[1:] != bs[:-1]) | (ss[1:] != ss[:-1])
    kept = bs[keep]
    row = ss[keep].astype(np.int64)
    counts = np.bincount(row, minlength=groups)
    max_len = _bucket_len(int(counts.max(initial=0)))
    full = np.bincount(ss, minlength=groups)
    full_len = _bucket_len(int(full.max(initial=0)))
    starts = np.zeros(groups, dtype=np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    col = np.arange(len(kept), dtype=np.int64) - np.repeat(starts, counts)
    padded = np.full((max_len, groups), -1, dtype=np.int32)
    padded[col, row] = kept
    return padded, order, keep, col, row, full_len


@lru_cache(maxsize=32)
def _fused_scan(levels: Geometry):
    """Jitted fused scan over grouped substreams for one geometry.

    Carry: ``(tags_o, age_o, …, merged?, t)`` — one dense ``(G, ways)``
    tag/age pair per outer level (in level order), then the merged
    ``(G, sum_r, 2W)`` inner carry when any level has ``R > 1``.  One
    step advances every group's next access through all levels with a
    single inner gather + scatter and emits its hit level (int8).
    """
    groups = fused_group_count(levels)
    lg = groups.bit_length() - 1
    k = len(levels)
    inner, w_max, offs, _ = _level_split(levels)
    gi = jnp.arange(groups)

    def step(carry, b):  # b: (groups,) int32, -1 = pad
        t = carry[-1]
        alive = b >= 0
        lvl = jnp.full(groups, k, dtype=jnp.int8)
        outs = list(carry[:-1])
        if inner:
            merged = outs[-1]
            # One gather for every inner level's accessed row.  Pads
            # (b == -1) read row ``offs`` and write it back unchanged
            # (the update is masked by ``alive``).
            idx = jnp.stack(
                [o + ((b >> lg) & (r - 1)) for (_, r, _), o in zip(inner, offs)],
                axis=1,
            )  # (groups, n_inner)
            rows = jnp.take_along_axis(merged, idx[:, :, None], axis=1)
        new_rows = []
        oj = ij = 0
        for i, (sets, ways) in enumerate(levels):
            if sets == groups:
                # Outer: the group's lanes *are* the set — no gather.
                row_t, row_a = outs[2 * oj], outs[2 * oj + 1]
            else:
                full = rows[:, ij]
                row_t = full[:, :ways]
                row_a = full[:, w_max : w_max + ways]
            hitv = row_t == b[:, None]
            hit = hitv.any(axis=1)
            # Fused victim select (one reduction, not argmax+argmin+where):
            # at most one hit lane per row, its INT32_MIN beats every age,
            # and ages are pairwise distinct per set — same unique winner.
            way = jnp.argmin(
                jnp.where(hitv, jnp.iinfo(jnp.int32).min, row_a), axis=1
            )
            onehot = (way[:, None] == jnp.arange(ways)[None, :]) & alive[:, None]
            nt = jnp.where(onehot, b[:, None], row_t)
            na = jnp.where(onehot, t, row_a)
            if sets == groups:
                outs[2 * oj] = nt
                outs[2 * oj + 1] = na
                oj += 1
            else:
                # Pad lanes ride through the scatter unchanged.
                new_rows.append(
                    jnp.concatenate(
                        [nt, full[:, ways:w_max], na, full[:, w_max + ways :]],
                        axis=1,
                    )
                )
                ij += 1
            lvl = jnp.where(alive & hit, jnp.int8(i), lvl)
            alive = alive & ~hit
        if inner:
            # One scatter for all inner levels; rows are disjoint by
            # construction (each level owns its ``offs`` range).
            outs[-1] = merged.at[gi[:, None], idx].set(jnp.stack(new_rows, axis=1))
        return tuple(outs) + (t + 1,), lvl

    @jax.jit
    def run(padded, *state):  # (max_len, groups) -> levels + final state
        init = tuple(state) + (jnp.int32(1),)
        final, lvls = jax.lax.scan(step, init, padded, unroll=4)
        return (lvls,) + final[:-1]

    return run


@lru_cache(maxsize=32)
def _fused_scan_batched(levels: Geometry):
    """The fused scan vmapped over a leading batch axis (one launch for a
    whole family of same-geometry streams)."""
    run = _fused_scan(levels)
    return jax.jit(jax.vmap(run))


def _resolve_states(
    levels: Sequence[Tuple[int, int]], states: Optional[Sequence[CacheState]]
) -> List[CacheState]:
    if states is None:
        return [init_state(s, w) for s, w in levels]
    assert len(states) == len(levels)
    return list(states)


def _grouped_state_args(states: Sequence[CacheState], groups: int):
    """Per-level ``(G, R*ways)`` lane pairs — the Pallas kernel's layout."""
    args = []
    for st in states:
        args.append(jnp.asarray(state_to_groups(st.tags, groups)))
        args.append(jnp.asarray(state_to_groups(st.age, groups)))
    return args


def _pack_state_args(states: Sequence[CacheState], levels: Geometry):
    """Pack per-level states into the host scan's carry layout."""
    groups = fused_group_count(levels)
    inner, w_max, offs, sum_r = _level_split(levels)
    args = []
    for (sets, ways), st in zip(levels, states):
        if sets == groups:
            args.append(jnp.asarray(state_to_groups(st.tags, groups)))
            args.append(jnp.asarray(state_to_groups(st.age, groups)))
    if inner:
        merged = np.full((groups, sum_r, 2 * w_max), _TAG_PAD, dtype=np.int32)
        merged[:, :, w_max:] = _AGE_PAD
        for (i, r, ways), o in zip(inner, offs):
            merged[:, o : o + r, :ways] = state_to_groups(
                states[i].tags, groups
            ).reshape(groups, r, ways)
            merged[:, o : o + r, w_max : w_max + ways] = state_to_groups(
                states[i].age, groups
            ).reshape(groups, r, ways)
        args.append(jnp.asarray(merged))
    return args


def _unpack_final_states(res, levels: Geometry) -> List[CacheState]:
    """Invert :func:`_pack_state_args` over a scan result and canonicalize.

    ``res`` is ``(lvls, *final_carry)``; batched callers pass one
    stream's slice.
    """
    groups = fused_group_count(levels)
    inner, w_max, offs, _ = _level_split(levels)
    finals: List[Optional[CacheState]] = [None] * len(levels)
    oi = 1
    for i, (sets, ways) in enumerate(levels):
        if sets == groups:
            tags = state_from_groups(np.asarray(res[oi]), sets, ways)
            age = state_from_groups(np.asarray(res[oi + 1]), sets, ways)
            finals[i] = canonicalize_state(tags, age)
            oi += 2
    if inner:
        merged = np.asarray(res[oi])
        for (i, r, ways), o in zip(inner, offs):
            sets = levels[i][0]
            tags = state_from_groups(
                merged[:, o : o + r, :ways].reshape(groups, r * ways), sets, ways
            )
            age = state_from_groups(
                merged[:, o : o + r, w_max : w_max + ways].reshape(groups, r * ways),
                sets,
                ways,
            )
            finals[i] = canonicalize_state(tags, age)
    return finals


def _skewed_padded(max_len: int, groups: int, stream_len: int) -> bool:
    """Padded-matrix blowup guard, evaluated on the *collapsed* matrix.

    Same budget as the per-level engine's: fall back when the padded
    cells exceed ``_PAD_FACTOR`` times the (original) stream length.
    Collapse only shrinks the matrix, so the fused path falls back
    strictly less often than a per-level pass over the same stream.
    """
    return max_len * groups > max(_PAD_FACTOR * stream_len, _PAD_FLOOR_CELLS)


def _fused_fallback(
    blocks: np.ndarray,
    levels: Sequence[Tuple[int, int]],
    states: List[CacheState],
    return_states: bool,
):
    """Per-level cascade on the fused-select machine (the plan-chooser
    and skew-guard path).

    Bit-identical to the fused scan by the engine contract: each level
    sees the miss substream of the level above, and canonical states
    compose across engines.  The passes run on
    :func:`~repro.memsim.engine.cache_pass_fused_select` — the same
    fused victim select as the scan — so the fused engine's cascade
    plan is itself faster than the frozen ``set_parallel`` comparator.
    """
    lvl = np.full(len(blocks), len(levels), dtype=np.int8)
    pos = np.arange(len(blocks), dtype=np.int64)
    sub = np.asarray(blocks)
    out_states = []
    for i, (sets, ways) in enumerate(levels):
        res = cache_pass_fused_select(sub, sets, ways, states[i], return_states)
        hit = res[0] if return_states else res
        if return_states:
            out_states.append(res[1])
        lvl[pos[hit]] = i
        pos = pos[~hit]
        sub = sub[~hit]
    if not return_states:
        return lvl
    return lvl, out_states


def _unpack_levels(
    n: int, lvls: np.ndarray, order, keep, col, row
) -> np.ndarray:
    """Scatter kept-access hit levels back to stream order; dropped run
    repeats are L1 hits (level 0) by construction."""
    sorted_lvl = np.zeros(n, dtype=np.int8)
    sorted_lvl[keep] = lvls[col, row]
    out = np.empty(n, dtype=np.int8)
    out[order] = sorted_lvl
    return out


def fused_cache_pass(
    blocks: np.ndarray,
    levels: Sequence[Tuple[int, int]],
    states: Optional[Sequence[CacheState]] = None,
    return_states: bool = False,
    use_pallas: Optional[bool] = None,
    force_scan: bool = False,
):
    """Run a stream through a fused K-level hierarchy in one carried scan.

    Returns the per-access **hit level** (int8: ``i`` = hit at
    ``levels[i]``, ``len(levels)`` = missed everywhere) and, with
    ``return_states=True``, the canonical per-level :class:`CacheState`
    carries — resumable by this or any per-level engine, bit-identically.
    ``use_pallas`` forces the Pallas kernel variant on or off (default:
    on when the backend is TPU).  ``force_scan`` bypasses the cost-based
    cascade fallback (the property tests use it to pin the carried-scan
    path on streams the plan chooser would route to the cascade); the
    skew guard still applies.
    """
    levels = tuple((int(s), int(w)) for s, w in levels)
    sts = _resolve_states(levels, states)
    if len(blocks) == 0:
        lvl = np.zeros(0, dtype=np.int8)
        if not return_states:
            return lvl
        return lvl, [CacheState(st.tags.copy(), st.age.copy()) for st in sts]
    groups = fused_group_count(levels)
    padded, order, keep, col, row, full_len = _group_collapse(blocks, groups)
    if _skewed_padded(padded.shape[0], groups, len(blocks)):
        return _fused_fallback(blocks, levels, sts, return_states)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas and not force_scan and padded.shape[0] * 4 > full_len:
        # Cost-based plan choice (host scan only; the Pallas kernel has
        # its own cost model on TPU): a fused step pays an inner-level
        # gather/scatter that a cascade step doesn't (~4x a first-level
        # cascade step on XLA-CPU), and the cascade's L2/LLC passes ride
        # on miss substreams far shorter than `full_len` — so the single
        # scan only wins its step-count bet when run collapse bought at
        # least two pow2 bucket halvings.  Short or run-light streams
        # take the bit-identical per-level cascade instead.
        return _fused_fallback(blocks, levels, sts, return_states)
    if use_pallas:
        from repro.kernels.cache_sim.cache_sim import interpret_mode
        from repro.kernels.cache_sim.fused_sim import fused_levels_pallas

        res = fused_levels_pallas(
            jnp.asarray(padded),
            levels,
            *_grouped_state_args(sts, groups),
            interpret=interpret_mode(),
        )
        lvls = np.asarray(res[0])
    else:
        res = _fused_scan(levels)(
            jnp.asarray(padded), *_pack_state_args(sts, levels)
        )
        lvls = np.asarray(res[0])
    out = _unpack_levels(len(blocks), lvls, order, keep, col, row)
    if not return_states:
        return out
    if use_pallas:
        finals = []
        for i, (sets, ways) in enumerate(levels):
            tags = state_from_groups(np.asarray(res[1 + 2 * i]), sets, ways)
            age = state_from_groups(np.asarray(res[2 + 2 * i]), sets, ways)
            finals.append(canonicalize_state(tags, age))
        return out, finals
    return out, _unpack_final_states(res, levels)


def fused_cache_pass_batch(
    streams: Sequence[np.ndarray],
    levels: Sequence[Tuple[int, int]],
    states: Optional[Sequence[Sequence[CacheState]]] = None,
    return_states: bool = False,
    force_scan: bool = False,
):
    """Batched fused pass over same-geometry streams: one vmapped launch.

    ``streams`` may differ in length; each is grouped (and run-collapsed)
    independently and padded to the family's common bucket length (pads
    are masked from every update and never gathered, so padding is exact,
    not approximate).  Returns one hit-level array per stream —
    bit-identical to looping :func:`fused_cache_pass` — plus per-stream
    canonical state lists with ``return_states=True``.  Streams that trip
    the set-skew guard (or an empty batch) fall back to the loop.
    """
    levels = tuple((int(s), int(w)) for s, w in levels)
    n = len(streams)
    sts = [
        _resolve_states(levels, None if states is None else states[i])
        for i in range(n)
    ]
    groups = fused_group_count(levels)
    grouped = (
        []
        if n == 0 or any(len(s) == 0 for s in streams)
        else [_group_collapse(s, groups) for s in streams]
    )
    if not grouped or any(
        _skewed_padded(g[0].shape[0], groups, len(s))
        for g, s in zip(grouped, streams)
    ) or (
        not force_scan
        and jax.default_backend() != "tpu"
        and any(g[0].shape[0] * 4 > g[5] for g in grouped)
    ):
        # Loop when any member is skewed or would not win as a fused
        # scan — each stream then makes its own plan choice.
        outs = [
            fused_cache_pass(
                streams[i], levels, sts[i], return_states,
                force_scan=force_scan,
            )
            for i in range(n)
        ]
        if not return_states:
            return outs
        return [o[0] for o in outs], [o[1] for o in outs]
    max_len = max(g[0].shape[0] for g in grouped)
    padded = np.full((n, max_len, groups), -1, dtype=np.int32)
    for i, g in enumerate(grouped):
        padded[i, : g[0].shape[0]] = g[0]
    per_stream = [_pack_state_args(s, levels) for s in sts]
    stacked = [
        jnp.asarray(np.stack([np.asarray(sa[j]) for sa in per_stream]))
        for j in range(len(per_stream[0]))
    ]
    res = _fused_scan_batched(levels)(jnp.asarray(padded), *stacked)
    lvls = np.asarray(res[0])
    outs = []
    for i, (_, order, keep, col, row, _full) in enumerate(grouped):
        outs.append(
            _unpack_levels(len(streams[i]), lvls[i], order, keep, col, row)
        )
    if not return_states:
        return outs
    final_states = [
        _unpack_final_states([np.asarray(r)[i] for r in res], levels)
        for i in range(n)
    ]
    return outs, final_states


def levels_to_hits(lvl: np.ndarray, k: int):
    """Unpack a hit-level array into the per-level hit masks of the
    cascaded path: mask ``i`` covers the miss substream of level ``i-1``
    (the full stream for ``i = 0``), exactly what
    :func:`~repro.memsim.hierarchy.simulate_demand` exposes."""
    masks = []
    sub = np.asarray(lvl)
    for i in range(k):
        hit = sub == i
        masks.append(hit)
        sub = sub[~hit]
    return masks


__all__ = [
    "fused_cache_pass",
    "fused_cache_pass_batch",
    "fused_group_count",
    "levels_to_hits",
    "state_from_groups",
    "state_to_groups",
]
