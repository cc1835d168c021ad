"""Plain reference of push BFS under the paper's §VI two-run protocol.

The base graph loses a random fifth of its vertices (run 1), then a tenth
of those present leave and a tenth of all vertices join (run 2).  Both runs
start at the vertex of highest degree present in both.  Each BFS level
visits its frontier in ascending vertex order; for a frontier vertex ``v``
Ligra's sparse edge map reads ``F[v]``, ``T[v]``, ``V[v]`` and then, for
each out-edge ``e``, ``N[e]`` and the destination's property ``P[dst]``.
Arrays lie in page-aligned regions (one guard page each) from
``0x10000000``, in the order F, T, V, N, P, NI; elements are 1, 8, 8, 4, 8
and 4 bytes wide; a cache line is 64 bytes.
"""
from __future__ import annotations

import numpy as np

from reference import graphs

BASE = 0x1000_0000
PAGE = 4096
F, T, V, N, P = 0, 1, 2, 3, 4
ELEM_BYTES = (1, 8, 8, 4, 8, 4)
MAX_LEVELS = 200


def layout(n: int, m: int) -> np.ndarray:
    """Base address of each array for ``n`` vertices and ``m`` edges."""
    sizes = (n, 8 * n, 8 * (n + 1), 4 * m, 8 * n, 4 * m)
    bases, addr = [], BASE
    for size in sizes:
        bases.append(addr)
        addr += (-(-size // PAGE) + 1) * PAGE
    return np.array(bases, dtype=np.int64)


def levels(g: graphs.Graph, root: int, present: np.ndarray) -> list:
    """BFS frontiers, each in ascending vertex order."""
    visited = np.zeros(g.num_vertices, dtype=bool)
    visited[root] = True
    frontier = np.array([root], dtype=np.int64)
    out = []
    while len(frontier) and len(out) < MAX_LEVELS:
        out.append(frontier)
        reached = np.zeros(g.num_vertices, dtype=bool)
        for v in frontier.tolist():
            reached[g.neighbors[g.offsets[v] : g.offsets[v + 1]]] = True
        new = reached & ~visited & present
        visited |= new
        frontier = np.flatnonzero(new).astype(np.int64)
    return out


def emit(g: graphs.Graph, frontier: np.ndarray, bases: np.ndarray) -> np.ndarray:
    """Byte addresses of one push level."""
    parts = []
    for v in frontier.tolist():
        lo, hi = int(g.offsets[v]), int(g.offsets[v + 1])
        head = bases[[F, T, V]] + v * np.array(ELEM_BYTES[:3], dtype=np.int64)
        edges = np.arange(lo, hi, dtype=np.int64)
        body = np.empty(2 * (hi - lo), dtype=np.int64)
        body[0::2] = bases[N] + 4 * edges
        body[1::2] = bases[P] + 8 * g.neighbors[lo:hi].astype(np.int64)
        parts += [head, body]
    return np.concatenate(parts) if parts else np.zeros(0, np.int64)


def trace(config: dict, seed: int, base_graph: graphs.Graph) -> dict:
    """The workload's line trace: ``blocks``, ``iter_id`` and ``eval_from``
    (the first access of run 2, where scoring starts)."""
    churn = config["churn"]
    n = base_graph.num_vertices
    m1, m2 = graphs.churn_masks(
        n, 2, seed, churn["init_frac"], churn["del_frac"], churn["add_frac"]
    )
    g1, g2 = graphs.induced(base_graph, m1), graphs.induced(base_graph, m2)
    root = int(np.argmax(np.where(m1 & m2, g1.degrees, -1)))
    bases = layout(n, max(g1.num_edges, g2.num_edges))
    addrs, iters, run_start = [], [], []
    it = 0
    for g, present in ((g1, m1), (g2, m2)):
        run_start.append(sum(len(a) for a in addrs))
        for frontier in levels(g, root, present):
            a = emit(g, frontier, bases)
            addrs.append(a)
            iters.append(np.full(len(a), it, dtype=np.int32))
            it += 1
    return dict(
        blocks=np.concatenate(addrs) >> 6,
        iter_id=np.concatenate(iters),
        eval_from=run_start[1],
    )
