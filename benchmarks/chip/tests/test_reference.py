"""The plain reference: its road graphs are the simulator's, and its
per-level emission and passes split by cache set give what a per-vertex
emitter and whole serial passes give."""
import json
from pathlib import Path

import numpy as np
import pytest

import check
from reference import bfs_two_run, cache, graphs, scoring

BENCH = Path(check.__file__).resolve().parent


@pytest.mark.parametrize("n,shortcut_frac,seed", [
    (20000, 0.0, 18),  # the simulator's "tinyroad"
    (5000, 0.05, 19),  # a small lattice with shortcuts, as "road-8m" has
])
def test_the_road_kind_is_the_simulators_road_graph(n, shortcut_frac, seed):
    from repro.graphs import road_graph

    want = road_graph(n, shortcut_frac=shortcut_frac, seed=seed)
    got = graphs.make_graph(
        {"kind": "road", "n": n, "shortcut_frac": shortcut_frac, "seed": seed}
    )
    np.testing.assert_array_equal(got.offsets, want.offsets)
    np.testing.assert_array_equal(got.neighbors, want.neighbors)
    assert got.neighbors.dtype == want.neighbors.dtype


# ------------------------------------------------ the serial forms, kept here


def serial_levels(g, root, present):
    visited = np.zeros(g.num_vertices, dtype=bool)
    visited[root] = True
    frontier = np.array([root], dtype=np.int64)
    out = []
    while len(frontier) and len(out) < bfs_two_run.MAX_LEVELS:
        out.append(frontier)
        reached = np.zeros(g.num_vertices, dtype=bool)
        for v in frontier.tolist():
            reached[g.neighbors[g.offsets[v]: g.offsets[v + 1]]] = True
        new = reached & ~visited & present
        visited |= new
        frontier = np.flatnonzero(new).astype(np.int64)
    return out


def serial_emit(g, frontier, bases):
    F, T, V, N, P = range(5)
    parts = []
    for v in frontier.tolist():
        lo, hi = int(g.offsets[v]), int(g.offsets[v + 1])
        head = bases[[F, T, V]] + v * np.array(bfs_two_run.ELEM_BYTES[:3], dtype=np.int64)
        edges = np.arange(lo, hi, dtype=np.int64)
        body = np.empty(2 * (hi - lo), dtype=np.int64)
        body[0::2] = bases[N] + 4 * edges
        body[1::2] = bases[P] + 8 * g.neighbors[lo:hi].astype(np.int64)
        parts += [head, body]
    return np.concatenate(parts) if parts else np.zeros(0, np.int64)


def serial_no_future(d, pf_blocks, pf_pos):
    future = {}
    for b, p in zip(d.l2_blocks[~d.l2_hit].tolist(), d.l2_miss_pos.tolist()):
        future[b] = p
    return np.array([future.get(b, -1) <= p
                     for b, p in zip(pf_blocks.tolist(), pf_pos.tolist())], dtype=bool)


@pytest.fixture(scope="module")
def tiny():
    """The ``bfs-tiny`` configuration of the check's tests."""
    config = json.loads((BENCH / "configs" / "bfs-amazon-scaled.json").read_text())
    config.update(
        name="bfs-tiny",
        dataset="tiny",
        graph={"kind": "powerlaw", "n": 3000, "m": 9000, "gamma": 2.2, "seed": 21},
    )
    return config


@pytest.mark.parametrize("seed", [7, 2**31 + 5])
def test_per_level_emission_is_the_per_vertex_emission(tiny, seed):
    g = graphs.make_graph(tiny["graph"])
    m1, m2 = graphs.churn_masks(g.num_vertices, 2, seed, 0.8, 0.1, 0.1)
    for mask in (m1, m2):
        run = graphs.induced(g, mask)
        root = int(np.argmax(np.where(m1 & m2, run.degrees, -1)))
        bases = bfs_two_run.layout(run.num_vertices, run.num_edges)
        got, want = bfs_two_run.levels(run, root, mask), serial_levels(run, root, mask)
        assert len(got) == len(want) > 3
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(bfs_two_run.emit(run, a, bases),
                                          serial_emit(run, b, bases))


@pytest.mark.parametrize("policy", ["lru", "fifo"])
def test_passes_split_by_set_are_the_serial_passes(tiny, monkeypatch, policy):
    monkeypatch.setattr(cache, "MIN_PARALLEL_EVENTS", 0)
    trace = bfs_two_run.trace(tiny, 2**31 + 11, graphs.make_graph(tiny["graph"]))
    h, tm = tiny["hierarchy"], tiny["timing"]
    rng = np.random.default_rng(3)
    serial = scoring.demand(trace["blocks"], h, policy)
    stream = (serial.l2_blocks[::3] + rng.integers(1, 9, len(serial.l2_pos[::3])),
              serial.l2_pos[::3], 4096)
    with cache.SetGroups(3) as groups:
        split = scoring.demand(trace["blocks"], h, policy, groups)
        for level in ("l1_hit", "l2_hit", "llc_hit", "l2_pos"):
            np.testing.assert_array_equal(getattr(split, level), getattr(serial, level))
        assert scoring.score(split, stream, trace["eval_from"], h, tm, groups) == (
            scoring.score(serial, stream, trace["eval_from"], h, tm))
        nl_blocks, nl_pos = scoring.nextline(serial)
        pf_blocks = np.concatenate([nl_blocks, stream[0]])
        pf_pos = np.concatenate([nl_pos, stream[1]])
        issuer = np.repeat(np.array([0, 1], np.int8), [len(nl_blocks), len(stream[0])])
        got = scoring.outcome(split, pf_blocks, pf_pos, issuer, h, policy, groups)
        want = scoring.outcome(serial, pf_blocks, pf_pos, issuer, h, policy)
    assert got.keys() == want.keys()
    for key in want:
        np.testing.assert_array_equal(got[key], want[key])
    order = np.argsort(pf_pos, kind="stable")
    np.testing.assert_array_equal(
        got["pf_no_future"], serial_no_future(serial, pf_blocks[order], pf_pos[order]))
    assert got["pf_no_future"].any() and not got["pf_no_future"].all()
