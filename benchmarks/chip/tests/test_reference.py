"""The plain reference: its road graphs are the simulator's, its
per-level emission and passes split by cache set give what a per-vertex
emitter and whole serial passes give, and its plain prefetch generators
issue the simulator's streams and equal the loops they stand for."""
import dataclasses
import json
import math
from pathlib import Path

import numpy as np
import pytest

import check
from reference import bfs_two_run, cache, graphs, prefetchers, scoring

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


# ------------------------------------------------ the plain prefetch generators


def loop_vldp(x):
    """VLDP as plain loops over epochs, pages and accesses."""
    epochs, pos = x.l2_epoch.tolist(), x.l2_pos.tolist()
    blocks, miss = x.l2_blocks.tolist(), x.l2_miss.tolist()
    out, tables = [], None
    for e in sorted(set(epochs)):
        pages = {}
        for i in range(len(pos)):
            if epochs[i] == e:
                pages.setdefault(blocks[i] // 64, []).append(i)
        candidates = [[] for _ in range(4)]
        counts = {1: {}, 2: {}, 3: {}, "opt": {}}
        for page in sorted(pages):
            offs = [blocks[i] % 64 for i in pages[page]]
            deltas = [None] + [offs[k] - offs[k - 1] for k in range(1, len(offs))]
            for k, i in enumerate(pages[page]):
                if tables is not None and miss[i]:
                    if k == 0:
                        pred = tables["opt"].get(offs[0])
                    else:
                        pred = None
                        for n in (3, 2, 1):
                            if k >= n and pred is None:
                                pred = tables[n].get(tuple(deltas[k - n + 1: k + 1]))
                    off, delta = offs[k], pred
                    for step in range(4):
                        if delta is None or not 0 <= off + delta < 64:
                            break
                        off += delta
                        candidates[step].append((page * 64 + off, pos[i]))
                        delta = tables[1].get((delta,))
                if k + 1 < len(offs):
                    after = deltas[k + 1]
                    keys = {n: tuple(deltas[k - n + 1: k + 1]) for n in (1, 2, 3) if k >= n}
                    if k == 0:
                        keys["opt"] = offs[0]
                    for n, key in keys.items():
                        counts[n][key, after] = counts[n].get((key, after), 0) + 1
        if tables is not None:
            flat = [c for step in candidates for c in step]
            keep = [True] * len(flat)
            last = None
            for k in sorted(range(len(flat)), key=lambda k: (flat[k], k)):
                if last is not None and last[0] == flat[k][0] and flat[k][1] - last[1] <= 1500:
                    keep[k] = False
                last = flat[k]
            out += [c for c, kept in zip(flat, keep) if kept]
        tables = {}
        for n, seen in counts.items():
            best = {}
            for (key, after), count in seen.items():
                if key not in best or (count, after) > best[key]:
                    best[key] = (count, after)
            tables[n] = {key: after for key, (count, after) in best.items()}
    return (np.array([b for b, _ in out], np.int64), np.array([p for _, p in out], np.int64), 0)


def loop_amc(x, max_misses_per_entry=20, lookahead_accesses=90, cache_bytes=24 * 1024):
    """AMC as plain loops over iterations, target reads and entries."""
    capacity = int(0.5 * x.input_bytes)
    lo = x.target_range[0] // 64
    hi = (x.target_range[0] + x.target_range[1]) // 64
    targets, misses = {}, {}
    for p, v, it in zip(x.target_pos.tolist(), x.target_vid.tolist(), x.target_iter.tolist()):
        targets.setdefault(it, []).append((p, v))
    for p, b, m, it in zip(x.l2_pos.tolist(), x.l2_blocks.tolist(), x.l2_miss.tolist(),
                           x.l2_iter.tolist()):
        if m and not lo <= b <= hi:
            misses.setdefault(it, []).append((p, b))

    def nbytes(table):
        return (sum(bits for _, bits, _ in table) + 7) // 8 + 12 * len(table)

    replaying, recording, out, read, written, current = {}, {}, [], 0, 0, None
    for it, (epoch, within) in enumerate(x.iter_epochs):
        if epoch != current:
            if current is not None:
                replaying, recording = recording, {}
            current = epoch
        reads = targets.get(it, [])
        if not reads:
            continue
        if replaying.get(within):
            table = replaying[within]
            gap = (reads[-1][0] - reads[0][0]) / (len(reads) - 1) if len(reads) > 1 else 1.0
            ahead = max(math.ceil(lookahead_accesses / max(gap, 1.0)), 1)
            matched = 0
            for j, (_, v) in enumerate(reads):
                held = 0
                for trigger, bits, blocks in table:
                    if trigger == v:
                        held += bits // 8
                        if held <= cache_bytes:
                            matched += bits // 8
                            out += [(b, reads[max(j - ahead, 0)][0]) for b in blocks]
            read += 12 * len(table) + matched
            written += matched
        entries = []
        for p, b in misses.get(it, []):
            owner = max((k for k, (tp, _) in enumerate(reads) if tp <= p), default=None)
            if owner is None:
                continue
            if entries and entries[-1][0] == owner and len(entries[-1][1]) < max_misses_per_entry:
                entries[-1][1].append(b)
            else:
                entries.append((owner, [b]))
        table = []
        for owner, blocks in entries:
            spread = max(abs(b - blocks[0]) for b in blocks)
            width = next((w for w, limit in ((1, 2**7), (2, 2**15), (4, 2**31))
                          if spread < limit), None)
            bits = 8 + 46 + (len(blocks) - 1) * 8 * width if width else 8 + 46 * len(blocks)
            table.append((reads[owner][1], bits, blocks))
        used = sum(nbytes(t) for t in recording.values())
        if used + nbytes(table) > capacity:
            budget, cost, keep = max(capacity - used, 0), 0, 0
            for _, bits, _ in table:
                cost += (bits + 7) // 8 + 12
                if cost > budget:
                    break
                keep += 1
            table = table[:keep]
        recording[within] = table
        written += nbytes(table)
    return (np.array([b for b, _ in out], np.int64), np.array([p for _, p in out], np.int64),
            read + written)


SWEEP = [p for job in json.loads((BENCH / "traffic" / "amc-sweep.json").read_text())["jobs"]
         for p in job["prefetchers"]]
VLDP = {"name": "vldp", "registry": "vldp", "overrides": {}}


@pytest.fixture(scope="module")
def program_and_plain(tiny):
    """The program's workload of ``bfs-tiny`` and the plain generators'
    inputs, from the reference's own trace, for the same seed."""
    import run_cell

    traffic = run_cell.Traffic(tiny, {"workload": "per_run", "jobs": [], "check_jobs": 1},
                               seed=2**31 + 4241)
    spec = traffic.spec(0)
    ref = check.Reference(tiny)
    trace, d = ref.simulate(spec.seed)
    x = prefetchers.Inputs.of(trace, d, scoring.baseline(d, tiny["hierarchy"]))
    return spec.build(), x, ref, spec.seed


def _program_stream(workload, entry):
    from repro.core.registry import get_prefetcher

    s = get_prefetcher(entry["registry"]).instantiate(**entry["overrides"])(workload)
    return s.blocks, s.pos, s.metadata_bytes


def _same(got, want):
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert got[2] == want[2]


@pytest.mark.parametrize("entry", [VLDP, *SWEEP], ids=lambda e: e["name"])
def test_the_plain_generators_issue_the_programs_streams(program_and_plain, entry):
    workload, x, ref, seed = program_and_plain
    got = _program_stream(workload, entry)
    plain = ref.stream(seed, entry)
    assert len(plain[0]) > 100
    ordered = prefetchers.GENERATORS[entry["registry"]].ordered
    assert check.stream_mismatch(got, plain, ordered) == 0
    if ordered:  # AMC: the same order, not only the same issues
        _same(got, plain)


def test_the_plain_amc_keeps_the_programs_storage_and_cache_caps(program_and_plain,
                                                                monkeypatch):
    """A recording space and an AMC cache small enough that both cut."""
    from repro.core.amc.prefetcher import AMCConfig, AMCPrefetcher
    from repro.core.amc.storage import AMCStorage

    workload, x, _, _ = program_and_plain
    small = dataclasses.replace(x, input_bytes=x.input_bytes // 40)
    entry = SWEEP[0]
    monkeypatch.setattr(prefetchers, "AMC_CACHE_BYTES", 40)
    plain = prefetchers.generate(entry, small)
    s = AMCPrefetcher(AMCConfig(**entry["overrides"], amc_cache_bytes=40)).generate(
        workload, storage=AMCStorage(int(0.5 * small.input_bytes)))
    _same((s.blocks, s.pos, s.metadata_bytes), plain)
    uncut = prefetchers.generate(entry, x)
    assert len(plain[0]) < len(uncut[0]) and plain[2] < uncut[2]
    _same(plain, loop_amc(small, **entry["overrides"], cache_bytes=40))


@pytest.mark.parametrize("entry", [VLDP, SWEEP[0], SWEEP[5], SWEEP[-1]],
                         ids=lambda e: e["name"])
def test_the_array_forms_are_the_loops(program_and_plain, entry):
    _, x, _, _ = program_and_plain
    loop = loop_vldp(x) if entry["registry"] == "vldp" else loop_amc(x, **entry["overrides"])
    _same(prefetchers.generate(entry, x), loop)


def test_the_plain_amc_issues_the_sharded_paths_stream(tmp_path, monkeypatch):
    """AMC on the tiny sharded cell, fed from the phase-1 spills."""
    import tempfile

    import run_cell

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    config = json.loads((BENCH / "configs" / "bfs-amazon-scaled.json").read_text())
    config.update(dataset="tinyroad",
                  graph={"kind": "road", "n": 20000, "shortcut_frac": 0.0, "seed": 18},
                  prefetchers=[p for p in config["prefetchers"] if p["name"] == "amc"])
    traffic = run_cell.Traffic(config, {"workload": "sharded", "shard_accesses": 1 << 14,
                                        "jobs": [{"prefetchers": "config"}],
                                        "check_jobs": 1}, seed=2**32 + 77)
    try:
        traffic.setup()
        job = traffic.run(0)
        ((_, got, entry),) = traffic.answers(job)["workloads"][0]["rows"]
    finally:
        traffic.close()
    plain = check.Reference(config).stream(traffic.spec(0).seed, entry)
    assert len(plain[0]) > 100
    _same(got, plain)
