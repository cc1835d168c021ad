"""Plain set-associative caches: one Python dict per set, in recency order.

A block maps to set ``block & (sets - 1)``.  ``policy="lru"`` is the
configuration's stated replacement (a hit makes the line most recent);
``policy="fifo"`` skips that refresh and serves as the correctness control.

Nothing in one set touches another, so a pass may be split by set:
:class:`SetGroups` runs each group of sets in a host process of its own and
puts the answers back in stream order, which is the same pass.
"""
from __future__ import annotations

import concurrent.futures
import multiprocessing
import os

import numpy as np

POLICIES = ("lru", "fifo")
# A pass over fewer events than this runs in the calling process.
MIN_PARALLEL_EVENTS = 1 << 20


def default_workers() -> int:
    """One process per core but one, at most 12."""
    return max(1, min(len(os.sched_getaffinity(0)) - 1, 12))


class SetGroups:
    """Host processes, each running a pass over one group of cache sets.

    A block's group is its set modulo ``workers``.  The processes are
    started (``spawn``: they import only numpy and this module) at the first
    pass long enough to split, and :meth:`close` stops them and waits.
    """

    def __init__(self, workers: int = 1):
        self.workers = workers
        self._pool = None

    def run(self, fn, sets: int, blocks: np.ndarray, *columns, args: tuple = ()):
        """``fn(blocks, *columns, sets, *args)`` over the whole stream."""
        if self.workers <= 1 or len(blocks) < MIN_PARALLEL_EVENTS:
            return fn(blocks, *columns, sets, *args)
        if self._pool is None:
            self._pool = concurrent.futures.ProcessPoolExecutor(
                self.workers, mp_context=multiprocessing.get_context("spawn")
            )
        group = ((blocks & (sets - 1)) % self.workers).astype(np.uint16)
        order = np.argsort(group, kind="stable")
        counts = np.bincount(group, minlength=self.workers)
        parts = np.split(order, np.cumsum(counts)[:-1])
        futures = [
            self._pool.submit(fn, blocks[idx], *(c[idx] for c in columns), sets, *args)
            for idx in parts
        ]
        answers = [future.result() for future in futures]
        if isinstance(answers[0], dict):
            return {k: _scatter(parts, [a[k] for a in answers]) for k in answers[0]}
        return _scatter(parts, answers)

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


SERIAL = SetGroups(1)


def _scatter(parts: list, values: list) -> np.ndarray:
    """Each group's answers back at its stream positions."""
    out = np.empty(sum(len(idx) for idx in parts), values[0].dtype)
    for idx, v in zip(parts, values):
        out[idx] = v
    return out


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


def hierarchy(
    blocks: np.ndarray, levels: list, policy: str = "lru", groups: SetGroups = SERIAL
) -> list:
    """Cascaded masks: level k sees the misses of level k-1, in order.

    ``levels`` is ``[(sets, ways), ...]`` from the innermost level out.
    """
    masks = []
    stream = blocks
    for sets, ways in levels:
        h = groups.run(hits, sets, stream, args=(ways, policy))
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
