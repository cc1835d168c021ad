"""Plain set-associative caches: one Python dict per set, in recency order.

A block maps to set ``block & (sets - 1)``.  ``policy="lru"`` is the
configuration's stated replacement (a hit makes the line most recent);
``policy="fifo"`` skips that refresh and serves as the correctness control.
"""
from __future__ import annotations

import numpy as np

POLICIES = ("lru", "fifo")


def _check(policy: str) -> bool:
    if policy not in POLICIES:
        raise ValueError(f"unknown policy {policy!r}; choose from {POLICIES}")
    return policy == "lru"


def hits(blocks: np.ndarray, sets: int, ways: int, policy: str = "lru") -> np.ndarray:
    """Hit mask of one cold pass over ``blocks``."""
    refresh = _check(policy)
    mask = sets - 1
    lines = [dict() for _ in range(sets)]
    out = np.zeros(len(blocks), dtype=bool)
    for i, b in enumerate(blocks.tolist()):
        s = lines[b & mask]
        if b in s:
            out[i] = True
            if refresh:
                del s[b]
                s[b] = None
        else:
            if len(s) >= ways:
                del s[next(iter(s))]
            s[b] = None
    return out


def hierarchy(blocks: np.ndarray, levels: list, policy: str = "lru") -> list:
    """Cascaded masks: level k sees the misses of level k-1, in order.

    ``levels`` is ``[(sets, ways), ...]`` from the innermost level out.
    """
    masks = []
    stream = blocks
    for sets, ways in levels:
        h = hits(stream, sets, ways, policy)
        masks.append(h)
        stream = stream[~h]
    return masks


def prefetch_pass(
    blocks: np.ndarray,
    is_pf: np.ndarray,
    pos2: np.ndarray,
    issuer: np.ndarray,
    sets: int,
    ways: int,
    fill_window2: int,
    policy: str = "lru",
) -> dict:
    """One cold pass over merged demand and prefetch events.

    Each resident line remembers whether every event since its fill was a
    prefetch (its prefetch bit) and which event filled it.  A demand hit on
    a line whose bit is set is *useful*; it is *late* when the fill was
    less than ``fill_window2`` (doubled positions) before it.  A prefetch
    that hits is *redundant*.  A prefetch fill whose block's next event
    misses was evicted *early*.
    """
    refresh = _check(policy)
    mask = sets - 1
    n = len(blocks)
    lines = [dict() for _ in range(sets)]
    hit = np.zeros(n, dtype=bool)
    useful = np.zeros(n, dtype=bool)
    late = np.zeros(n, dtype=bool)
    redundant = np.zeros(n, dtype=bool)
    early = np.zeros(n, dtype=bool)
    fill_issuer = np.full(n, -1, dtype=np.int8)
    last_event = {}  # block -> (event index, it was a prefetch fill)
    pf = is_pf.tolist()
    p2 = pos2.tolist()
    iss = issuer.tolist()
    for k, b in enumerate(blocks.tolist()):
        s = lines[b & mask]
        line = s.get(b)
        prev = last_event.get(b)
        if line is not None:
            hit[k] = True
            if refresh:
                del s[b]
                s[b] = line
            if pf[k]:
                redundant[k] = True
            else:
                if line[0]:
                    useful[k] = True
                    fill = line[1]
                    late[k] = p2[fill] + fill_window2 > p2[k]
                    fill_issuer[k] = iss[fill]
                line[0] = False
            last_event[b] = (k, False)
        else:
            if prev is not None and prev[1]:
                early[prev[0]] = True
            if len(s) >= ways:
                del s[next(iter(s))]
            s[b] = [pf[k], k]
            last_event[b] = (k, pf[k])
    return dict(
        hit=hit,
        useful=useful,
        late=late,
        redundant=redundant,
        early=early,
        fill_issuer=fill_issuer,
    )
