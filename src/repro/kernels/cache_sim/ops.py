"""Public API: full-stream cache pass through the Pallas set-parallel kernel.

Reuses the engine's stable group-by-set partitioning so the kernel, the
batched-scan engine, and the serial reference all consume identical padded
substreams — the kernel only changes *where* the per-set machines run.

Backend gating: on TPU the kernel compiles natively; on CPU it runs in
interpret mode, which validates semantics (tests) but is not a fast path.
Any other backend raises (:func:`interpret_mode`).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.kernels.cache_sim.cache_sim import (
    LANES,
    interpret_mode,
    lru_hits,
    lru_hits_carry,
)
from repro.kernels.cache_sim.ref import lru_hits_ref

__all__ = ["cache_pass_pallas", "lru_hits", "lru_hits_carry", "lru_hits_ref"]


def cache_pass_pallas(
    blocks: np.ndarray,
    sets: int,
    ways: int,
    set_tile: Optional[int] = None,
    interpret: Optional[bool] = None,
    state=None,
    return_state: bool = False,
):
    """Hit mask of one cache level, computed by the Pallas kernel.

    Same contract (and bit-identical output) as
    :func:`repro.memsim.engine.cache_pass`, including the canonical
    :class:`~repro.memsim.engine.CacheState` carry for chunked passes.
    """
    from repro.core.obs import spans as obs  # lazy: avoids import cycle
    from repro.memsim import engine

    if len(blocks) == 0:
        hits = np.zeros(0, dtype=bool)
        if not return_state:
            return hits
        st = state if state is not None else engine.init_state(sets, ways)
        return hits, engine.CacheState(st.tags.copy(), st.age.copy())
    if interpret is None:
        interpret = interpret_mode()
    with obs.span("cache_pass.group"):
        padded, order, col, row = engine.group_by_set(blocks, sets)
        st = state if state is not None else engine.init_state(sets, ways)
    with obs.span("cache_pass.device"):
        hits, tags1, age1 = lru_hits_carry(
            jnp.asarray(padded),
            jnp.asarray(st.tags),
            jnp.asarray(st.age),
            set_tile=set_tile or LANES,
            interpret=interpret,
        )
        hits = np.asarray(hits)
    with obs.span("cache_pass.scatter"):
        out = np.zeros(len(blocks), dtype=bool)
        out[order] = hits[col, row].astype(bool)
        if not return_state:
            return out
        return out, engine.canonicalize_state(np.asarray(tags1), np.asarray(age1))
