"""Plain reference of push BFS under the paper's §VI two-run protocol.

The base graph loses a random fifth of its vertices (run 1), then a tenth
of those present leave and a tenth of all vertices join (run 2).  Both runs
start at the vertex of highest degree present in both.  Each BFS level
visits its frontier in ascending vertex order; for a frontier vertex ``v``
Ligra's sparse edge map reads ``F[v]``, ``T[v]``, ``V[v]`` and then, for
each out-edge ``e``, ``N[e]`` and the destination's property ``P[dst]``.
Arrays lie in page-aligned regions (one guard page each) from
``0x10000000``, in the order F, T, V, N, P, NI; elements are 1, 8, 8, 4, 8
and 4 bytes wide; a cache line is 64 bytes.  The application's input is
F, T, V, N and P; T is the target array whose reads AMC correlates with
the misses that follow them.  Each run is one epoch, and each BFS level
one iteration of it.
"""
from __future__ import annotations

import numpy as np

from reference import graphs

BASE = 0x1000_0000
PAGE = 4096
F, T, V, N, P = 0, 1, 2, 3, 4
ELEM_BYTES = (1, 8, 8, 4, 8, 4)
INPUT_ARRAYS = (F, T, V, N, P)
MAX_LEVELS = 200


def sizes(n: int, m: int) -> tuple:
    """Bytes of each array for ``n`` vertices and ``m`` edges."""
    return (n, 8 * n, 8 * (n + 1), 4 * m, 8 * n, 4 * m)


def layout(n: int, m: int) -> np.ndarray:
    """Base address of each array for ``n`` vertices and ``m`` edges."""
    bases, addr = [], BASE
    for size in sizes(n, m):
        bases.append(addr)
        addr += (-(-size // PAGE) + 1) * PAGE
    return np.array(bases, dtype=np.int64)


def edges_of(g: graphs.Graph, frontier: np.ndarray) -> np.ndarray:
    """The out-edge indices of ``frontier``, vertex by vertex in its order."""
    lo = g.offsets[frontier]
    deg = g.offsets[frontier + 1] - lo
    before = np.cumsum(deg) - deg  # edges of the earlier frontier vertices
    return np.repeat(lo - before, deg) + np.arange(int(deg.sum()), dtype=np.int64)


def levels(g: graphs.Graph, root: int, present: np.ndarray) -> list:
    """BFS frontiers, each in ascending vertex order."""
    visited = np.zeros(g.num_vertices, dtype=bool)
    visited[root] = True
    frontier = np.array([root], dtype=np.int64)
    out = []
    while len(frontier) and len(out) < MAX_LEVELS:
        out.append(frontier)
        reached = np.zeros(g.num_vertices, dtype=bool)
        reached[g.neighbors[edges_of(g, frontier)]] = True
        new = reached & ~visited & present
        visited |= new
        frontier = np.flatnonzero(new).astype(np.int64)
    return out


def emit(g: graphs.Graph, frontier: np.ndarray, bases: np.ndarray) -> np.ndarray:
    """Byte addresses of one push level: per frontier vertex, in order, its
    three head reads and then one (N, P) pair per out-edge."""
    deg = g.offsets[frontier + 1] - g.offsets[frontier]
    size = 3 + 2 * deg
    start = np.cumsum(size) - size
    out = np.empty(int(size.sum()), dtype=np.int64)
    for k in (F, T, V):
        out[start + k] = bases[k] + frontier * ELEM_BYTES[k]
    edges = edges_of(g, frontier)
    before = np.cumsum(deg) - deg
    body = np.repeat(start + 3 - 2 * before, deg) + 2 * np.arange(len(edges))
    out[body] = bases[N] + 4 * edges
    out[body + 1] = bases[P] + 8 * g.neighbors[edges].astype(np.int64)
    return out


def trace(config: dict, seed: int, base_graph: graphs.Graph) -> dict:
    """The workload's line trace: ``blocks``, ``iter_id`` and ``eval_from``
    (the first access of run 2, where scoring starts); the target reads'
    positions and vertices (``target_pos``, ``target_vid``) and the target
    array's ``target_range`` (base, bytes); ``iter_epochs``, each
    iteration's (epoch, index within it); and ``input_bytes``."""
    churn = config["churn"]
    n = base_graph.num_vertices
    m1, m2 = graphs.churn_masks(
        n, 2, seed, churn["init_frac"], churn["del_frac"], churn["add_frac"]
    )
    g1, g2 = graphs.induced(base_graph, m1), graphs.induced(base_graph, m2)
    root = int(np.argmax(np.where(m1 & m2, g1.degrees, -1)))
    m = max(g1.num_edges, g2.num_edges)
    bases, size = layout(n, m), sizes(n, m)
    addrs, iters, run_start, iter_epochs = [], [], [], []
    it = 0
    for run, (g, present) in enumerate(((g1, m1), (g2, m2))):
        run_start.append(sum(len(a) for a in addrs))
        for level, frontier in enumerate(levels(g, root, present)):
            a = emit(g, frontier, bases)
            addrs.append(a)
            iters.append(np.full(len(a), it, dtype=np.int32))
            iter_epochs.append((run, level))
            it += 1
    addr = np.concatenate(addrs)
    del addrs
    target = (addr >= bases[T]) & (addr < bases[T] + size[T])
    target_pos = np.flatnonzero(target)
    return dict(
        blocks=addr >> 6,
        iter_id=np.concatenate(iters),
        eval_from=run_start[1],
        target_pos=target_pos,
        target_vid=(addr[target_pos] - bases[T]) // ELEM_BYTES[T],
        target_range=(int(bases[T]), size[T]),
        iter_epochs=iter_epochs,
        input_bytes=sum(size[k] for k in INPUT_ARRAYS),
    )
