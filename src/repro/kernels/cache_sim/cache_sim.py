"""Set-parallel LRU cache simulation as a Pallas TPU kernel.

The set-parallel engine (:mod:`repro.memsim.engine`) turns the cache pass
into ``sets`` independent short simulations over a padded ``(L, sets)``
substream matrix (time-major, one column per set).  The kernel keeps that
layout: sets go on lanes (up to 128 per tile), each set's ways on
sublanes, and time on the major axis, so every step reads and writes one
whole row of the tile — never a dynamic lane slice.

Time streams through VMEM in fixed chunks along a sequential
(``"arbitrary"``) grid axis.  The tag/age state lives in VMEM scratch from
a set tile's first chunk to its last: it is seeded from the carried state
at chunk 0 and written out at the last chunk, so VMEM use is bounded by
the chunk, not the trace.  Ages are stamped with the global step
(``chunk offset + t + 1``) and so keep increasing across chunks.  Pad steps
(``b == -1``) emit a (never-gathered) hit bit but are masked out of the
update.

The way select is two int32 min-reductions over a sublane iota, not an
``argmax``/``argmin`` (which Mosaic lowers for float32 only): the hit way
is the lowest index with a matching tag, the victim the lowest index whose
age equals the set's minimum — the same lowest-index tie-break as the
reference scan's ``argmax``/``argmin``.  The update is a one-hot select, so
no dynamic scatters either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # set lanes per tile (one vreg row)
SUBLANES = 8  # time steps per row group (one vreg of the stream tile)
TIME_CHUNK = 1024  # time steps per grid step: 512 KiB per int32 stream tile


def min_index(mask: jnp.ndarray, iota: jnp.ndarray) -> jnp.ndarray:
    """Lowest sublane index where ``mask`` holds, per lane (``(1, n)``);
    ``iota.shape[0]`` where it holds nowhere."""
    return jnp.min(jnp.where(mask, iota, iota.shape[0]), axis=0, keepdims=True)


def lru_select(tags, age, b, iota):
    """Hit flag and touched way of one LRU step (ways on sublanes).

    ``tags``/``age`` are ``(ways, n)``, ``b`` is ``(1, n)``.  The way is
    the hit way on a hit and the LRU victim (lowest index among the
    oldest) on a miss.
    """
    hitv = tags == b
    hit_way = min_index(hitv, iota)
    hit = hit_way < iota.shape[0]
    victim = min_index(age == jnp.min(age, axis=0, keepdims=True), iota)
    return hit, jnp.where(hit, hit_way, victim)


def interpret_mode() -> bool:
    """Whether the kernels run interpreted on this backend.

    They compile on ``tpu`` and are interpreted on ``cpu`` (where the tests
    run them).  Any other backend is an error: interpreting there would
    pass off a slow emulation as the device path.
    """
    backend = jax.default_backend()
    if backend in ("tpu", "cpu"):
        return backend == "cpu"
    raise RuntimeError(
        f"the Pallas cache kernels compile for tpu and are interpreted on "
        f"cpu; backend {backend!r} is neither"
    )


def time_chunk(length: int) -> int:
    """Chunk of the time axis one grid step holds in VMEM."""
    return min(TIME_CHUNK, -(-length // SUBLANES) * SUBLANES)


def pad_time(padded: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """Tail-pad the time axis with ``-1`` to a whole number of chunks."""
    extra = -padded.shape[0] % chunk
    return jnp.pad(padded, ((0, extra), (0, 0)), constant_values=-1)


def _lru_chunk_kernel(
    blocks_ref, tags_in_ref, age_in_ref, hits_ref, tags_out_ref, age_out_ref,
    tags_sc, age_sc,
):
    # blocks_ref / hits_ref: (chunk, set_tile) — this chunk of the tile's
    # substreams.  tags_*/age_*: (ways, set_tile), resident across chunks.
    j = pl.program_id(1)
    chunk = blocks_ref.shape[0]

    @pl.when(j == 0)
    def _():
        tags_sc[...] = tags_in_ref[...]
        age_sc[...] = age_in_ref[...]

    iota = jax.lax.broadcasted_iota(jnp.int32, tags_sc.shape, 0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, blocks_ref.shape[1]), 0)
    t0 = j * chunk

    def body(i, carry):
        tags, age = carry
        r0 = pl.multiple_of(i * SUBLANES, SUBLANES)
        tile = blocks_ref[pl.ds(r0, SUBLANES), :]
        hits = jnp.zeros(tile.shape, jnp.int32)
        for r in range(SUBLANES):
            b = tile[r : r + 1, :]  # (1, set_tile)
            hit, way = lru_select(tags, age, b, iota)
            onehot = (iota == way) & (b >= 0)
            tags = jnp.where(onehot, b, tags)
            age = jnp.where(onehot, t0 + r0 + r + 1, age)
            hits = jnp.where(rows == r, hit.astype(jnp.int32), hits)
        hits_ref[pl.ds(r0, SUBLANES), :] = hits
        return tags, age

    tags, age = jax.lax.fori_loop(
        0, chunk // SUBLANES, body, (tags_sc[...], age_sc[...])
    )
    tags_sc[...] = tags
    age_sc[...] = age

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        tags_out_ref[...] = tags
        age_out_ref[...] = age


@functools.partial(jax.jit, static_argnames=("set_tile", "interpret"))
def lru_hits_carry(
    padded: jnp.ndarray,  # (L, sets) int32 substream matrix, tail-padded -1
    tags0: jnp.ndarray,  # (sets, ways) int32 carried tags (-1 empty)
    age0: jnp.ndarray,  # (sets, ways) int32 carried ages
    set_tile: int = LANES,
    interpret: bool = False,
):
    """Hit mask ``(L, sets)`` plus final (raw) ``(sets, ways)`` tag/age
    state, resuming from a carry."""
    length, sets = padded.shape
    ways = tags0.shape[1]
    set_tile = min(set_tile, sets)
    assert sets % set_tile == 0, (sets, set_tile)
    chunk = time_chunk(length)
    stream = pad_time(padded.astype(jnp.int32), chunk)
    grid = (sets // set_tile, stream.shape[0] // chunk)
    stream_spec = pl.BlockSpec((chunk, set_tile), lambda i, j: (j, i))
    state_spec = pl.BlockSpec((ways, set_tile), lambda i, j: (0, i))
    state_shape = jax.ShapeDtypeStruct((ways, sets), jnp.int32)
    hits, tags1, age1 = pl.pallas_call(
        _lru_chunk_kernel,
        grid=grid,
        in_specs=[stream_spec, state_spec, state_spec],
        out_specs=[stream_spec, state_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct(stream.shape, jnp.int32),
            state_shape,
            state_shape,
        ],
        scratch_shapes=[
            pltpu.VMEM((ways, set_tile), jnp.int32),  # tags
            pltpu.VMEM((ways, set_tile), jnp.int32),  # ages
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(stream, tags0.T, age0.T)
    return hits[:length], tags1.T, age1.T


def lru_hits(
    padded: jnp.ndarray,  # (L, sets) int32 substream matrix, tail-padded -1
    ways: int,
    set_tile: int = LANES,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-cell hit mask ``(L, sets)`` (int32 0/1) from a cold cache."""
    sets = padded.shape[1]
    tags0 = jnp.full((sets, ways), -1, jnp.int32)
    age0 = jnp.broadcast_to(jnp.arange(-ways, 0, dtype=jnp.int32), (sets, ways))
    return lru_hits_carry(
        padded, tags0, age0, set_tile=set_tile, interpret=interpret
    )[0]
