"""Input graphs and their evolution, made from a configuration's numbers.

This is the benchmark's own data generator: the same draws, in the same
order, as the graph generators that the simulator uses for its named
datasets (R-MAT, configuration-model power law, road lattice) and for the
paper's §VI
vertex churn.  The reference runs on these graphs; the simulator makes its
own from the dataset name, so a change to the simulator's data shows up as
a trace that differs from this one.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    """CSR adjacency: ``offsets`` (n+1,) int64, ``neighbors`` (m,) int32."""

    offsets: np.ndarray
    neighbors: np.ndarray

    @property
    def num_vertices(self) -> int:
        return len(self.offsets) - 1

    @property
    def num_edges(self) -> int:
        return len(self.neighbors)

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.offsets)

    def edge_sources(self) -> np.ndarray:
        return np.repeat(np.arange(self.num_vertices, dtype=np.int32), self.degrees)


def from_edges(src, dst, n: int, dedup: bool = True) -> Graph:
    """CSR from an edge list: self loops dropped, duplicates dropped."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    if dedup and len(src):
        key = src * n + dst
        order = np.argsort(key, kind="stable")
        key = key[order]
        uniq = np.ones(len(key), dtype=bool)
        uniq[1:] = key[1:] != key[:-1]
        src, dst = src[order][uniq], dst[order][uniq]
    else:
        order = np.argsort(src, kind="stable")
        src, dst = src[order], dst[order]
    counts = np.bincount(src, minlength=n).astype(np.int64)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    return Graph(offsets, dst.astype(np.int32))


def _trim(g: Graph, m: int, rng, n: int) -> Graph:
    if g.num_edges <= m:
        return g
    keep = np.sort(rng.choice(g.num_edges, size=m, replace=False))
    return from_edges(g.edge_sources()[keep], g.neighbors[keep], n, dedup=False)


def rmat(n: int, m: int, a: float, seed: int) -> Graph:
    """R-MAT with b = c = 0.35 (1 - a), oversampled 1.35x, trimmed to m."""
    rng = np.random.default_rng(seed)
    scale = int(np.ceil(np.log2(max(n, 2))))
    b = c = (1.0 - a) * 0.35
    probs = np.array([a, b, c, 1.0 - a - b - c])
    draws = int(m * 1.35)
    src = np.zeros(draws, dtype=np.int64)
    dst = np.zeros(draws, dtype=np.int64)
    for _ in range(scale):
        q = rng.choice(4, size=draws, p=probs)
        src = (src << 1) | (q >> 1)
        dst = (dst << 1) | (q & 1)
    perm = rng.permutation(1 << scale)
    g = from_edges(perm[src] % n, perm[dst] % n, n)
    return _trim(g, m, rng, n)


def powerlaw(n: int, m: int, gamma: float, seed: int) -> Graph:
    """Configuration model with Zipf-like weights, oversampled 1.25x."""
    rng = np.random.default_rng(seed)
    w = np.arange(1, n + 1, dtype=np.float64) ** (-1.0 / (gamma - 1.0))
    rng.shuffle(w)
    w /= w.sum()
    src = rng.choice(n, size=int(m * 1.25), p=w)
    dst = rng.choice(n, size=int(m * 1.25), p=w)
    return _trim(from_edges(src, dst, n), m, rng, n)


def road(n: int, shortcut_frac: float, seed: int) -> Graph:
    """A square lattice of ``int(sqrt(n))**2`` vertices, each lattice edge
    in both directions, plus ``shortcut_frac`` of the vertex count in
    directed shortcuts between uniform endpoints."""
    rng = np.random.default_rng(seed)
    side = int(np.sqrt(n))
    n = side * side
    idx = np.arange(n).reshape(side, side)
    right = np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], axis=1)
    down = np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], axis=1)
    lattice = np.concatenate([right, down])
    edges = np.concatenate([lattice, lattice[:, ::-1]])
    n_short = int(n * shortcut_frac)
    src = rng.integers(0, n, size=n_short)
    dst = rng.integers(0, n, size=n_short)
    edges = np.concatenate([edges, np.stack([src, dst], axis=1)])
    return from_edges(edges[:, 0], edges[:, 1], n)


def make_graph(spec: dict) -> Graph:
    """The graph a configuration's ``graph`` entry describes."""
    if spec["kind"] == "rmat":
        return rmat(spec["n"], spec["m"], spec["a"], spec["seed"])
    if spec["kind"] == "powerlaw":
        return powerlaw(spec["n"], spec["m"], spec["gamma"], spec["seed"])
    if spec["kind"] == "road":
        return road(spec["n"], spec["shortcut_frac"], spec["seed"])
    raise ValueError(f"unknown graph kind {spec['kind']!r}")


def churn_masks(
    n: int, epochs: int, seed: int, init: float, delete: float, add: float
) -> list:
    """Present-vertex masks per epoch under uniform vertex churn."""
    rng = np.random.default_rng(seed)
    mask = np.zeros(n, dtype=bool)
    mask[rng.choice(n, size=int(init * n), replace=False)] = True
    out = [mask]
    for _ in range(epochs - 1):
        cur = out[-1].copy()
        inside = np.flatnonzero(cur)
        outside = np.flatnonzero(~cur)
        n_del = int(delete * len(inside))
        n_add = min(int(add * n), len(outside))
        cur[rng.choice(inside, size=n_del, replace=False)] = False
        cur[rng.choice(outside, size=n_add, replace=False)] = True
        out.append(cur)
    return out


def induced(g: Graph, keep: np.ndarray) -> Graph:
    """Subgraph on the ``keep`` vertices, in the original id space."""
    src = g.edge_sources()
    e_keep = keep[src] & keep[g.neighbors]
    return from_edges(src[e_keep], g.neighbors[e_keep], g.num_vertices, dedup=False)
