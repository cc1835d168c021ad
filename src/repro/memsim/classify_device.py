"""Prefetch-event classification as one jitted program over the merged L2 stream.

The device form of three host steps of scoring one prefetch stream:
:func:`repro.memsim.scan_cache.classify_prefetch_events` (useful, late,
redundant, early, fill origin), :func:`repro.memsim.hierarchy._no_future_demand`
(a prefetch whose block no later baseline L2 miss demands) and the unmerge
of the merged stream back into demand order and prefetch order.  The host
forms stay as the CPU path and as the oracle the tests compare against.

One sort by (block, stream index) — stable on the block — makes each
block's event chain contiguous in stream order; every per-event quantity
is then a scan in that order.  "No future demand" needs no second sort:
demand events sit at doubled positions ``2q`` and prefetches at ``2p+1``,
so a later event of the prefetch's chain that is a baseline-miss demand is
exactly a demand at ``q > p`` — the strict test of the host form.  One
scatter to each event's rank among the prefetches or among the demands
undoes the sort and unmerges in one step.

Shapes are padded to pow2 buckets (:func:`repro.memsim.engine._bucket_len`),
so the program compiles O(log N) times.  Padded events carry the largest
int32 block id, so they sort after every real chain, and are L2 hits with
no baseline miss, so they neither end a real chain's fill early nor count as
a future demand.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.memsim.engine import _bucket_len

_I32_MAX = int(np.iinfo(np.int32).max)


def fits_int32(max_block: int, max_pos2: int, fill_window: int) -> bool:
    """Whether a merged stream can run in the int32 program (x64 is off):
    block ids, and doubled positions plus the fill window, below 2**31."""
    return max_block <= _I32_MAX and max_pos2 + fill_window <= _I32_MAX


def _shift_down(x, k, fill):
    """``y[i] = x[i - k]``, ``fill`` below ``k``."""
    return jnp.concatenate([jnp.full((k,), fill, x.dtype), x[:-k]])


def _shift_up(x, k, fill):
    """``y[i] = x[i + k]``, ``fill`` from ``n - k`` on."""
    return jnp.concatenate([x[k:], jnp.full((k,), fill, x.dtype)])


def _scan(op, x, identity, reverse=False):
    """Inclusive scan by log2(n) contiguous shifts.  Over 2^21 elements
    the TPU compiler takes 14-55 s for one ``lax.cumsum``/``cummax`` and
    seconds for this form, which runs in about 0.45 ms on a TPU v5e."""
    shift = _shift_up if reverse else _shift_down
    k = 1
    while k < x.shape[0]:
        x = op(x, shift(x, k, identity))
        k *= 2
    return x


def _carry_last(flag, values):
    """Each value at the last event at or before ``i`` whose ``flag`` is
    set: the same shifted scan over (flag, values) pairs."""
    k = 1
    while k < flag.shape[0]:
        values = [
            jnp.where(flag, v, _shift_down(v, k, 0)) for v in values
        ]
        flag = flag | _shift_down(flag, k, False)
        k *= 2
    return values


@jax.jit
def _classify_program(blocks, pos2, hit, base_miss, issuer, fill_window):
    """Classify and unmerge one padded merged stream.

    Every input is per merged event, in stream order: ``base_miss`` marks
    the demand events the baseline missed in L2, ``issuer`` is -1 on
    demand events.  Returns the flags (bit 0 L2 hit, then useful, late,
    redundant, early, no future demand) and the fill issuer of every
    event: the first ``npf`` entries are the prefetches in stream order,
    the next ``nd`` the demand events.
    """
    n = blocks.shape[0]
    i32 = jnp.int32
    idx = lax.iota(i32, n)
    is_pf = (pos2 & 1) == 1
    # Where each event goes in the output: prefetches, then demands.
    pf_count = _scan(jnp.add, is_pf.astype(i32), 0)
    dest = jnp.where(is_pf, pf_count - 1, pf_count[-1] + idx - pf_count)
    packed = (
        hit.astype(i32)
        | base_miss.astype(i32) << 1
        | (issuer.astype(i32) + 1) << 8
    )

    # Block chains contiguous, stream order inside.  The TPU gathers one
    # random element at a time, so the sort carries every operand and
    # nothing below gathers.
    b, dest, p, packed = lax.sort(
        (blocks, dest, pos2, packed), num_keys=1, is_stable=True
    )
    h = (packed & 1) != 0
    miss = ~h
    bm = (packed & 2) != 0
    f = (p & 1) == 1
    chain_start = _shift_down(b, 1, -1) != b  # block ids are >= 0

    # Chains start with a miss (cold caches), so scans that look back to
    # the last fill (miss) never cross a chain boundary.
    last_fill = _scan(jnp.maximum, jnp.where(miss, idx, -1), -1)
    last_demand = _scan(jnp.maximum, jnp.where(f, -1, idx), -1)
    # The line's pf bit after each event: every event since the fill was a
    # prefetch; a hit reads it as left by the chain's previous event.
    all_pf_since_fill = last_demand < last_fill
    prev_all_pf = _shift_down(all_pf_since_fill, 1, False) & ~chain_start
    fill_pos2, fill_issuer = _carry_last(miss, [p, (packed >> 8) - 1])

    useful = h & ~f & prev_all_pf
    # A useful event is a hit, so its last fill is the prefetch fill itself.
    late = useful & (fill_pos2 + fill_window > p)
    redundant = f & h
    early = miss & f & _shift_up(miss & ~chain_start, 1, False)
    fill_issuer = jnp.where(useful, fill_issuer, -1)

    # No future demand: the next baseline-miss demand strictly after each
    # event lies beyond the end of its chain (reverse scans, segmented by
    # comparing against the chain's last index).
    chain_last = _shift_up(chain_start, 1, True)
    chain_end = _scan(jnp.minimum, jnp.where(chain_last, idx, n), n, reverse=True)
    next_bm = _shift_up(
        _scan(jnp.minimum, jnp.where(bm, idx, n), n, reverse=True), 1, n
    )
    no_future = next_bm > chain_end

    out = (
        h.astype(i32)
        | useful.astype(i32) << 1
        | late.astype(i32) << 2
        | redundant.astype(i32) << 3
        | early.astype(i32) << 4
        | no_future.astype(i32) << 5
        | (fill_issuer + 1) << 8
    )
    # Back to stream order and unmerged, in one scatter; one byte of flags
    # and one of issuer per event, since the copy to the host dominates.
    out = jnp.zeros_like(out).at[dest].set(out, unique_indices=True)
    return (out & 0xFF).astype(jnp.uint8), ((out >> 8) - 1).astype(jnp.int8)


@dataclasses.dataclass
class L2Outcome:
    """One scored stream's L2 classification, unmerged into demand order
    and prefetch order (what both classification paths return)."""

    demand_l2_hit: np.ndarray
    demand_useful: np.ndarray
    demand_late: np.ndarray
    demand_fill_issuer: np.ndarray  # int8, -1 where not useful
    pf_l2_hit: np.ndarray
    pf_redundant: np.ndarray
    pf_early: np.ndarray
    pf_no_future: np.ndarray


def _pad(x: np.ndarray, n: int, fill, dtype) -> np.ndarray:
    out = np.empty(n, dtype=dtype)
    out[: len(x)] = x
    out[len(x):] = fill
    return out


def classify_chains(
    blocks: np.ndarray,
    pos2: np.ndarray,
    issuer: np.ndarray,
    hit: np.ndarray,
    demand_slots: np.ndarray,
    base_l2_hit: np.ndarray,
    fill_window: int,
) -> L2Outcome:
    """Run :func:`_classify_program` on one merged stream.

    ``blocks``, ``pos2`` (doubled positions: ``2q`` demand, ``2p+1``
    prefetch, non-decreasing), ``issuer`` (-1 on demands) and ``hit``
    cover the merged stream; ``demand_slots`` are its demand events and
    ``base_l2_hit`` their baseline L2 hits.  The caller checks
    :func:`fits_int32` first.
    """
    total = len(blocks)
    npf = total - len(demand_slots)
    n = _bucket_len(total)
    base_miss = np.zeros(n, dtype=bool)
    base_miss[demand_slots] = ~base_l2_hit
    flags, fill_issuer = jax.device_get(
        _classify_program(
            _pad(blocks, n, _I32_MAX, np.int32),
            _pad(pos2, n, 0, np.int32),
            _pad(hit, n, True, bool),
            base_miss,
            _pad(issuer, n, -1, np.int8),
            np.int32(fill_window),
        )
    )
    pf, dem = flags[:npf], flags[npf:total]
    return L2Outcome(
        demand_l2_hit=(dem & 1) != 0,
        demand_useful=(dem & 2) != 0,
        demand_late=(dem & 4) != 0,
        demand_fill_issuer=fill_issuer[npf:total],
        pf_l2_hit=(pf & 1) != 0,
        pf_redundant=(pf & 8) != 0,
        pf_early=(pf & 16) != 0,
        pf_no_future=(pf & 32) != 0,
    )
