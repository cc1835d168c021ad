"""Fused multi-level LRU hierarchy simulation as a Pallas TPU kernel.

The fused engine (:mod:`repro.memsim.fused`) carries every hierarchy
level's tag/age lanes in one scan over group substreams.  On TPU the
grouped layout maps the way the single-level kernel
(:mod:`repro.kernels.cache_sim.cache_sim`) maps sets: groups go on lanes,
time on the major axis of the ``(L, groups)`` substream matrix, and each
level's ``R·ways`` (relative set, way) rows on sublanes.  Time streams
through VMEM in fixed chunks along a sequential grid axis with every
level's state held in VMEM scratch from the first chunk to the last; ages
are stamped with the global step.  The L1/L2/LLC update is pure VPU work
per step, and the emitted value is the *hit level* (0 = outermost level
… K = missed everywhere) rather than a single level's hit bit.

Unlike the host-side fused scan (which gathers only the accessed set's
ways per step — the right trade on CPU), the kernel keeps each level's
full ``R·ways`` rows live and masks by the accessed relative set; the
masked min-reductions pick the lowest (relative set, way) row, the
reference tie-break.  Pads (``b == -1``) emit a (never-gathered) level
but are masked out of every update.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cache_sim.cache_sim import (
    LANES,
    SUBLANES,
    min_index,
    pad_time,
    time_chunk,
)

_INTMAX = jnp.iinfo(jnp.int32).max


def _fused_chunk_kernel(levels, blocks_ref, *refs):
    # refs: (tags_in, age_in) per level, lvl_ref, (tags_out, age_out) per
    # level, then (tags, age) VMEM scratch per level.  blocks_ref / lvl_ref
    # block: (chunk, group_tile); state blocks: (R·ways, group_tile).
    k = len(levels)
    groups = min(sets for sets, _ in levels)
    lg = groups.bit_length() - 1
    ins, lvl_ref = refs[: 2 * k], refs[2 * k]
    outs, state = refs[2 * k + 1 : 4 * k + 1], refs[4 * k + 1 :]
    j = pl.program_id(1)
    chunk = blocks_ref.shape[0]

    @pl.when(j == 0)
    def _():
        for src, dst in zip(ins, state):
            dst[...] = src[...]

    iotas = [
        jax.lax.broadcasted_iota(jnp.int32, state[2 * i].shape, 0)
        for i in range(k)
    ]
    rows = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, blocks_ref.shape[1]), 0)
    t0 = j * chunk

    def body(n, carry):
        r0 = pl.multiple_of(n * SUBLANES, SUBLANES)
        tile = blocks_ref[pl.ds(r0, SUBLANES), :]
        lvls = jnp.zeros(tile.shape, jnp.int32)
        for r in range(SUBLANES):
            b = tile[r : r + 1, :]  # (1, group_tile)
            alive = b >= 0
            lvl = jnp.full(b.shape, k, jnp.int32)
            for i, (sets, ways) in enumerate(levels):
                tags = state[2 * i][...]
                age = state[2 * i + 1][...]
                iota = iotas[i]
                rel = (b >> lg) & ((sets // groups) - 1)
                inset = (iota // ways) == rel
                hit_way = min_index((tags == b) & inset, iota)
                hit = hit_way < iota.shape[0]
                masked = jnp.where(inset, age, _INTMAX)
                victim = min_index(
                    masked == jnp.min(masked, axis=0, keepdims=True), iota
                )
                onehot = (iota == jnp.where(hit, hit_way, victim)) & alive
                state[2 * i][...] = jnp.where(onehot, b, tags)
                state[2 * i + 1][...] = jnp.where(onehot, t0 + r0 + r + 1, age)
                lvl = jnp.where(alive & hit, jnp.int32(i), lvl)
                alive = alive & ~hit
            lvls = jnp.where(rows == r, lvl, lvls)
        lvl_ref[pl.ds(r0, SUBLANES), :] = lvls
        return carry

    jax.lax.fori_loop(0, chunk // SUBLANES, body, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for src, dst in zip(state, outs):
            dst[...] = src[...]


@functools.partial(
    jax.jit, static_argnames=("levels", "group_tile", "interpret")
)
def fused_levels_pallas(
    padded: jnp.ndarray,  # (L, groups) int32 group substreams, tail-padded -1
    levels,  # ((sets, ways), ...) outer→inner, static
    *state,  # tags0, age0 per level, each (groups, R·ways) int32
    group_tile: int = LANES,
    interpret: bool = False,
):
    """Hit levels plus final (raw) per-level states, resuming from carries.

    Returns ``(lvls, tags_0, age_0, …)`` with ``lvls`` ``(L, groups)``
    int32 and each state ``(groups, R·ways)`` — the host scan's stream
    layout and :func:`repro.memsim.fused.state_to_groups`' state layout,
    so the engine's scatter/canonicalize epilogue is shared between
    backends.
    """
    length, groups = padded.shape
    group_tile = min(group_tile, groups)
    assert groups % group_tile == 0, (groups, group_tile)
    chunk = time_chunk(length)
    stream = pad_time(padded.astype(jnp.int32), chunk)
    grid = (groups // group_tile, stream.shape[0] // chunk)
    stream_spec = pl.BlockSpec((chunk, group_tile), lambda i, j: (j, i))
    rows = [st.shape[1] for st in state]
    state_specs = [
        pl.BlockSpec((n, group_tile), lambda i, j: (0, i)) for n in rows
    ]
    out = pl.pallas_call(
        functools.partial(_fused_chunk_kernel, tuple(levels)),
        grid=grid,
        in_specs=[stream_spec] + state_specs,
        out_specs=[stream_spec] + state_specs,
        out_shape=[jax.ShapeDtypeStruct(stream.shape, jnp.int32)]
        + [jax.ShapeDtypeStruct((n, groups), jnp.int32) for n in rows],
        scratch_shapes=[pltpu.VMEM((n, group_tile), jnp.int32) for n in rows],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(stream, *(st.T for st in state))
    return (out[0][:length],) + tuple(st.T for st in out[1:])
