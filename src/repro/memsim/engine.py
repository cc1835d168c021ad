"""Set-parallel cache simulation engine.

Two exact equivalences let the serial one-access-per-step simulator become
a batch of short, concurrent per-set simulations:

1. **Set independence.** A set-associative cache partitions blocks by set
   index ``b & (sets - 1)`` and replacement state never crosses sets, so a
   stable group-by sort of the access stream by set yields ``sets``
   independent substreams whose hit masks compose (scatter back through
   the sort order) into the full-stream hit mask.
2. **Stack distance ≡ LRU.** Within one set under true LRU, an access hits
   iff its stack distance — the number of *distinct* blocks touched in the
   set since that block's previous access — is ``< ways`` (first touches
   are cold misses).  Hits are a property of each substream alone, so the
   per-set machines need no coordination: the ``(max_len, sets)`` padded
   matrix of substreams is advanced one access per step for *every* set at
   once, and the sequential dependence chain drops from N steps to
   ``max_len`` (~N/sets) steps of fully vectorized work.

Engines (pick with ``REPRO_CACHE_ENGINE``, :func:`set_engine`, or the
:func:`use_engine` context manager):

- ``fused``: the set-parallel machine with *all* hierarchy levels carried
  in one scan (:mod:`repro.memsim.fused`) — ``simulate_demand`` runs
  L1→L2→LLC as a single launch emitting per-access hit levels when the
  cost-based plan chooser finds run collapse shrank the padded bucket
  (otherwise the bit-identical per-level cascade), and the *batched*
  scoring entry points (``simulate_with_prefetch_batch``,
  ``cache_pass_batch``) collapse a prefetcher family's per-stream level
  passes into one vmapped launch per level with a fused victim select.
  Single-stream scoring and single-level ``cache_pass`` calls have
  nothing to batch and run the set-parallel cascade.
- ``set_parallel``: the padded batched ``lax.scan`` described above.  Hit
  masks are bit-identical to the reference — the per-set age counters
  preserve the reference's relative LRU order and tie-breaking
  (``argmin``/``argmax`` pick the lowest way index in both) — so
  ``TRACE_CODE_VERSION`` and every persisted workload artifact stay valid.
- ``reference``: the original serial ``lax.scan``
  (:mod:`repro.memsim.scan_cache`), kept as the correctness oracle the
  property tests and the bench parity gate compare against — including
  across shard seams (see *carried state* below).
- ``pallas``: the same set-parallel machine as a Pallas TPU kernel
  (:mod:`repro.kernels.cache_sim`): sets on lanes, time streamed through
  VMEM in chunks, the tag/age carry in VMEM scratch.  It compiles on TPU
  and runs in interpret mode on CPU (tests only: correct, not fast); any
  other backend raises.

The default engine is resolved per backend: ``pallas`` on TPU (the kernel
is the native scoring path on the chip), ``fused`` everywhere else.
``REPRO_CACHE_ENGINE`` overrides the resolution either way.

**Carried state.**  Sharded traces stream through the simulator one chunk
at a time, so every engine can resume a pass exactly where the previous
chunk left off: ``cache_pass(..., state=..., return_state=True)`` threads a
:class:`CacheState` in and out.  The returned state is *canonical* — per
set, ways are re-aged to ``-ways..-1`` with empty ways first (in way-index
order) and filled ways in LRU→MRU order — which makes it engine-independent
(every engine emits the same canonical state for the same stream prefix)
and makes resuming bit-identical to an uninterrupted pass: carried lines
are strictly older than any new access (new passes count age from 1), and
``argmin`` tie-breaking still prefers the lowest-index empty way.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from functools import lru_cache
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.memsim import scan_cache

ENGINES = ("fused", "set_parallel", "reference", "pallas")
ENGINE_ENV = "REPRO_CACHE_ENGINE"
# Default off TPU; see default_engine() for the backend-aware resolution.
DEFAULT_ENGINE = "fused"

_override: Optional[str] = None


def _check(name: str) -> str:
    if name not in ENGINES:
        raise ValueError(f"unknown cache engine {name!r}; choose from {ENGINES}")
    return name


@lru_cache(maxsize=1)
def default_engine() -> str:
    """Backend-resolved default: the Pallas kernel on TPU, the fused
    hierarchy engine elsewhere.  A backend that fails to initialize is an
    error, not a reason to carry on as CPU."""
    return "pallas" if jax.default_backend() == "tpu" else DEFAULT_ENGINE


def current_engine() -> str:
    """The active engine: ``set_engine`` override > env var > default."""
    if _override is not None:
        return _override
    env = os.environ.get(ENGINE_ENV)
    return _check(env) if env is not None else default_engine()


def set_engine(name: Optional[str]) -> None:
    """Select the cache engine process-wide (``None`` restores env/default)."""
    global _override
    _override = _check(name) if name is not None else None


@contextlib.contextmanager
def use_engine(name: str) -> Iterator[None]:
    """Run the enclosed block under a specific cache engine."""
    global _override
    prev, _override = _override, _check(name)
    try:
        yield
    finally:
        _override = prev


@dataclasses.dataclass
class CacheState:
    """Canonical tag/LRU carry of one cache level between chunked passes.

    ``tags`` is ``(sets, ways)`` int32 (-1 = empty way); ``age`` is
    ``(sets, ways)`` int32 in the canonical form produced by
    :func:`canonicalize_state`.  Engine-independent: resuming any engine
    from this state is bit-identical to an uninterrupted pass.
    """

    tags: np.ndarray
    age: np.ndarray

    @property
    def sets(self) -> int:
        return self.tags.shape[0]

    @property
    def ways(self) -> int:
        return self.tags.shape[1]


def init_state(sets: int, ways: int) -> CacheState:
    """Canonical all-empty state (what a cold pass starts from)."""
    tags = np.full((sets, ways), -1, dtype=np.int32)
    age = np.tile(np.arange(-ways, 0, dtype=np.int32), (sets, 1))
    return CacheState(tags, age)


def canonicalize_state(tags: np.ndarray, age: np.ndarray) -> CacheState:
    """Re-age raw engine tag/age arrays into the canonical carry form.

    Per set, ways are ranked empties-first (in way-index order, preserving
    the ``argmin`` tie-break of a fresh pass) then filled ways by ascending
    raw age (LRU -> MRU), and assigned ages ``rank - ways`` — all negative,
    so a resumed pass (ages counted from 1) always sees carried lines as
    older than anything it inserts.  Only the per-set *order* of the raw
    ages matters, which is why engines with different age-counter schedules
    (serial stream counter vs padded step counter) canonicalize to the
    same state.
    """
    tags = np.asarray(tags, dtype=np.int32)
    ways = tags.shape[1]
    key = np.where(
        tags == -1, np.iinfo(np.int64).min, np.asarray(age, dtype=np.int64)
    )
    order = np.argsort(key, axis=1, kind="stable")
    rank = np.empty_like(order)
    np.put_along_axis(rank, order, np.arange(ways, dtype=order.dtype)[None, :], axis=1)
    return CacheState(tags.copy(), (rank - ways).astype(np.int32))


def group_by_set(
    blocks: np.ndarray, sets: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Partition a stream into padded per-set substream columns.

    Returns ``(padded, order, col, row)``: ``padded`` is ``(max_len, sets)``
    int32 with each set's substream (in stream order) occupying a column
    prefix, tail-padded with ``-1``; ``order`` is the stable group-by sort
    permutation, and ``padded[col, row]`` are the real accesses in sorted
    order — scatter per-cell results back with ``out[order] = res[col, row]``.

    Tail padding is harmless by construction: pad cells are masked out of
    the tag/age update (``b >= 0`` guard), so they neither perturb a set's
    state nor the carried state returned to the caller, and their hit bits
    are never gathered.
    """
    blocks = np.asarray(blocks)
    # Guard here so every engine entry point (set-parallel, Pallas ops)
    # inherits it: an id >= 2**31 would wrap negative in int32, alias the
    # -1 empty-way/pad sentinel, and silently corrupt the hit mask.
    assert blocks.max(initial=0) < 2**31, "block ids must fit in int32"
    assert sets <= 1 << 16, "set index must fit the uint16 radix-sort key"
    b32 = blocks.astype(np.int32)
    s = b32 & np.int32(sets - 1)
    # uint16 sort key routes numpy's stable argsort to its O(N) radix
    # path (stable sorts of >16-bit ints fall back to timsort) — same
    # permutation, ~4x faster on paper-scale streams.
    order = np.argsort(s.astype(np.uint16), kind="stable")
    counts = np.bincount(s, minlength=sets)
    max_len = _bucket_len(int(counts.max()))
    starts = np.zeros(sets, dtype=np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    col = np.arange(len(b32), dtype=np.int64) - np.repeat(starts, counts)
    row = s[order].astype(np.int64)
    padded = np.full((max_len, sets), -1, dtype=np.int32)
    padded[col, row] = b32[order]
    return padded, order, col, row


def _bucket_len(n: int) -> int:
    """Round the padded substream length up to a power of two (min 128).

    The batched pass is jitted per ``(sets, ways, max_len)`` shape; pow2
    bucketing caps compile count at O(log N) per geometry instead of one
    compile per distinct trace length.
    """
    return max(128, 1 << (n - 1).bit_length())


@lru_cache(maxsize=32)
def _batched_pass(sets: int, ways: int):
    """Jitted batched scan: every step advances all ``sets`` machines.

    Takes the carried tag/age arrays as traced inputs and returns the
    final state alongside the hit matrix; pad steps (``b == -1``) emit a
    (never-gathered) bit but are masked out of the state update.
    """

    def step(carry, b):
        tags, age, t = carry  # (sets, ways), (sets, ways), scalar
        hitv = tags == b[:, None]
        hit = hitv.any(axis=1)
        way = jnp.where(hit, jnp.argmax(hitv, axis=1), jnp.argmin(age, axis=1))
        onehot = (way[:, None] == jnp.arange(tags.shape[1])[None, :]) & (
            b >= 0
        )[:, None]
        tags = jnp.where(onehot, b[:, None], tags)
        age = jnp.where(onehot, t, age)
        return (tags, age, t + 1), hit

    @jax.jit
    def run(padded, tags0, age0):  # (max_len, sets) -> hits + final state
        init = (tags0, age0, jnp.int32(1))
        (tags1, age1, _), hits = jax.lax.scan(step, init, padded, unroll=4)
        return hits, tags1, age1

    return run


# Skew guard: the padded matrix costs max_len x sets cells.  Balanced
# streams stay within ~2x of N (pow2 bucketing), so beyond PAD_FACTOR x N
# cells (with an absolute floor so tiny streams never trip it) the stream
# is set-skewed enough that the serial reference's O(N) machine wins —
# and a fully-degenerate stream (every access in one set at a large-sets
# geometry) would otherwise demand a max_len x sets allocation far larger
# than the stream itself.
_PAD_FACTOR = 4
_PAD_FLOOR_CELLS = 1 << 22


def cache_pass_set_parallel(
    blocks: np.ndarray,
    sets: int,
    ways: int,
    state: Optional[CacheState] = None,
    return_state: bool = False,
):
    counts = np.bincount(
        np.asarray(blocks, dtype=np.int64) & (sets - 1), minlength=sets
    )
    cells = _bucket_len(int(counts.max(initial=0))) * sets
    if cells > max(_PAD_FACTOR * len(blocks), _PAD_FLOOR_CELLS):
        # bit-identical fallback (canonical states compose across engines)
        return scan_cache.cache_pass(blocks, sets, ways, state, return_state)
    from repro.core.obs import spans as obs  # lazy: avoids import cycle

    with obs.span("cache_pass.group"):
        padded, order, col, row = group_by_set(blocks, sets)
        st = state if state is not None else init_state(sets, ways)
    with obs.span("cache_pass.device"):
        hits, tags1, age1 = _batched_pass(sets, ways)(
            jnp.asarray(padded), jnp.asarray(st.tags), jnp.asarray(st.age)
        )
        hits = np.asarray(hits)
    with obs.span("cache_pass.scatter"):
        out = np.zeros(len(blocks), dtype=bool)
        out[order] = hits[col, row]
        if not return_state:
            return out
        return out, canonicalize_state(np.asarray(tags1), np.asarray(age1))


def _fused_select_pass(sets: int, ways: int):
    """Set-parallel scan with a *fused victim select* — the fused
    engine's pass machine (batched scoring and the cascade plan).

    :func:`_batched_pass` picks the touched way with three vector ops
    (``argmax`` over the hit lanes, ``argmin`` over ages, a ``where``
    select).  Here they collapse into one reduction::

        way = argmin(where(hitv, INT32_MIN, age))

    Bit-identical by construction: tags are unique within a set, so
    ``hitv`` has at most one lane set — on a hit that lane's ``INT32_MIN``
    beats every age (ages are ``>= -ways``), on a miss the expression *is*
    ``argmin(age)``, and ages are pairwise distinct per set so both forms
    share the same unique minimum (no tie-break to preserve).  One
    reduction instead of two plus a select cuts the per-step cost ~2x at
    L2 geometry and ~3x at LLC geometry on CPU.  The per-level
    ``set_parallel`` path keeps the original formulation: it is this PR's
    frozen comparator for the fused-vs-per-level bench cell.
    """

    def step(carry, b):
        tags, age, t = carry
        hitv = tags == b[:, None]
        hit = hitv.any(axis=1)
        way = jnp.argmin(
            jnp.where(hitv, jnp.iinfo(jnp.int32).min, age), axis=1
        )
        onehot = (way[:, None] == jnp.arange(tags.shape[1])[None, :]) & (
            b >= 0
        )[:, None]
        tags = jnp.where(onehot, b[:, None], tags)
        age = jnp.where(onehot, t, age)
        return (tags, age, t + 1), hit

    def run(padded, tags0, age0):
        init = (tags0, age0, jnp.int32(1))
        (tags1, age1, _), hits = jax.lax.scan(step, init, padded, unroll=4)
        return hits, tags1, age1

    return run


@lru_cache(maxsize=32)
def _fused_select_vmapped(sets: int, ways: int):
    """:func:`_fused_select_pass` vmapped over a leading stream axis — one
    launch advances a whole family of same-geometry streams."""
    return jax.jit(jax.vmap(_fused_select_pass(sets, ways)))


@lru_cache(maxsize=32)
def _fused_select_single(sets: int, ways: int):
    """:func:`_fused_select_pass` jitted for one stream — the fused
    engine's per-level machine when its plan chooser picks the cascade."""
    return jax.jit(_fused_select_pass(sets, ways))


def cache_pass_fused_select(
    blocks: np.ndarray,
    sets: int,
    ways: int,
    state: Optional[CacheState] = None,
    return_state: bool = False,
):
    """One-level pass on the fused-select machine (fused engine only).

    Same contract and bit-identical output as
    :func:`cache_pass_set_parallel` (see :func:`_fused_select_pass` for
    the identity argument); kept separate so the ``set_parallel`` engine
    — this PR's frozen A/B comparator — is never touched by fused-path
    optimizations.  Skewed streams fall back to the serial reference.
    """
    if _pad_skewed(blocks, sets):
        return scan_cache.cache_pass(blocks, sets, ways, state, return_state)
    padded, order, col, row = group_by_set(blocks, sets)
    st = state if state is not None else init_state(sets, ways)
    hits, tags1, age1 = _fused_select_single(sets, ways)(
        jnp.asarray(padded), jnp.asarray(st.tags), jnp.asarray(st.age)
    )
    hits = np.asarray(hits)
    out = np.zeros(len(blocks), dtype=bool)
    out[order] = hits[col, row]
    if not return_state:
        return out
    return out, canonicalize_state(np.asarray(tags1), np.asarray(age1))


def _pad_skewed(blocks: np.ndarray, sets: int) -> bool:
    counts = np.bincount(
        np.asarray(blocks, dtype=np.int64) & (sets - 1), minlength=sets
    )
    cells = _bucket_len(int(counts.max(initial=0))) * sets
    return cells > max(_PAD_FACTOR * len(blocks), _PAD_FLOOR_CELLS)


def cache_pass_batch(streams, sets: int, ways: int):
    """One cold-state pass per stream through one level, vmapped over the
    family.

    ``streams`` may differ in length; each is grouped by set
    independently, then streams whose padded substreams land in the same
    pow2 bucket share one vmapped :func:`_fused_select_pass` launch —
    batching never pads a short stream to a longer member's bucket, so the
    batched scan does exactly the work of the per-stream loop, minus the
    per-stream dispatches.  Returns one hit mask per stream, bit-identical
    to looping :func:`cache_pass` — which is also the fallback for empty
    or set-skewed members.  This is the scoring path's batching primitive:
    the per-prefetcher level passes of one workload family collapse into
    one dispatch per level per bucket instead of one per stream.
    """
    n = len(streams)
    if n == 0:
        return []
    if n == 1 or any(len(s) == 0 for s in streams) or any(
        _pad_skewed(s, sets) for s in streams
    ):
        return [cache_pass(s, sets, ways) for s in streams]
    grouped = [group_by_set(s, sets) for s in streams]
    st = init_state(sets, ways)
    by_bucket: dict = {}
    for i, g in enumerate(grouped):
        by_bucket.setdefault(g[0].shape[0], []).append(i)
    outs: list = [None] * n
    for idxs in by_bucket.values():
        k = len(idxs)
        padded = np.stack([grouped[i][0] for i in idxs])
        tags0 = jnp.asarray(np.broadcast_to(st.tags, (k,) + st.tags.shape))
        age0 = jnp.asarray(np.broadcast_to(st.age, (k,) + st.age.shape))
        hits, _, _ = _fused_select_vmapped(sets, ways)(
            jnp.asarray(padded), tags0, age0
        )
        hits = np.asarray(hits)
        for j, i in enumerate(idxs):
            _, order, col, row = grouped[i]
            out = np.zeros(len(streams[i]), dtype=bool)
            out[order] = hits[j][col, row]
            outs[i] = out
    return outs


def cache_pass(
    blocks: np.ndarray,
    sets: int,
    ways: int,
    state: Optional[CacheState] = None,
    return_state: bool = False,
):
    """Run an access stream through one cache level; returns the hit mask.

    Dispatches to the active engine (see module docstring); every engine
    honors the same contract and produces bit-identical masks.  With
    ``state=`` the pass resumes from a carried :class:`CacheState` (as
    returned by a prior ``return_state=True`` call) and is bit-identical
    to one uninterrupted pass over the concatenated stream.
    """
    if len(blocks) == 0:
        hits = np.zeros(0, dtype=bool)
        if not return_state:
            return hits
        st = state if state is not None else init_state(sets, ways)
        return hits, CacheState(st.tags.copy(), st.age.copy())
    assert blocks.max(initial=0) < 2**31, "block ids must fit in int32"
    engine = current_engine()
    if engine == "reference":
        return scan_cache.cache_pass(blocks, sets, ways, state, return_state)
    if engine == "pallas":
        from repro.kernels.cache_sim.ops import cache_pass_pallas

        return cache_pass_pallas(blocks, sets, ways, state=state,
                                 return_state=return_state)
    # "fused" only changes multi-level simulation (repro.memsim.hierarchy
    # routes whole hierarchies through repro.memsim.fused); a single-level
    # pass has nothing to fuse, so it runs on the set-parallel machine.
    return cache_pass_set_parallel(blocks, sets, ways, state, return_state)


__all__ = [
    "ENGINES",
    "ENGINE_ENV",
    "CacheState",
    "cache_pass",
    "cache_pass_batch",
    "cache_pass_fused_select",
    "cache_pass_set_parallel",
    "canonicalize_state",
    "current_engine",
    "default_engine",
    "group_by_set",
    "init_state",
    "set_engine",
    "use_engine",
]
