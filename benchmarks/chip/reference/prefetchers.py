"""Plain prefetch-stream generators: AMC and VLDP as the simulator models them.

Each generator reads :class:`Inputs`, made from the reference's own trace,
demand masks and next-line baseline, and returns the stream that the
simulator's generator of the same registry entry issues: ``(blocks, pos,
metadata_bytes)``, a prefetch of line ``blocks[i]`` issued at trace
position ``pos[i]``.  ``check.py`` compares the two streams.  The forms
here work on whole arrays; the loops they stand for are kept in
``tests/test_reference.py`` and tested equal.

**VLDP** (``vldp``), as the simulator models it, not as the MICRO'15 paper
builds it.  Per epoch (here one BFS run), the L2 accesses (the L1 misses)
are split by 4-KiB page, each page's accesses kept in stream order, and
the deltas between consecutive line offsets in a page form its history.
Four tables, trained on the previous epoch's L2 accesses, map a history to
the delta that follows it: DPT1 (the last delta), DPT2 (the last two),
DPT3 (the last three), and OPT (a page's first offset to its first
delta).  Each maps a key to the next value seen most often after it; a tie
goes to the largest.  An L2 access that is a demand miss beside next-line
triggers: the first access of a page asks OPT, a later one DPT3, else
DPT2, else DPT1, taking the longest history the page has.  Up to four
lines follow, each the last plus a delta: the predicted one, then DPT1 of
the delta before, until a line leaves the page or DPT1 has no entry.  The
epoch's candidates, all first lines, then all second lines and so on,
each in page order, are filtered: a candidate is dropped when the
previous candidate of the same line, in (line, position) order, lies at
most 1,500 accesses earlier, whether that one was kept or not.  VLDP
stores its tables on chip, so its metadata traffic is 0.

Where this differs from the paper: the tables are trained offline on the
previous epoch and hold the majority next delta, where the paper updates
them online per access with accuracy counters; every entry predicts, with
no confidence threshold; triggers are L2 demand misses, not every L2
access; chaining past the first line looks up DPT1 with the last
predicted delta, where the paper shifts the predicted delta into the
history and asks the longest table again; the 1,500-access filter is the
simulator's stand-in for lines already in flight or resident, which the
paper has no need of.

**AMC** (``amc``), with the ``max_misses_per_entry`` and
``lookahead_accesses`` of ``AMCConfig`` as overrides.  Each epoch (one BFS
run) records, per iteration (one BFS level): the L2 demand misses beside
next-line, leaving out lines of the target array, that fall at or after a
target read and before the next one form that read's entry, split after
every ``max_misses_per_entry`` misses and tagged with the target vertex;
misses before the iteration's first target read are dropped.  An entry
costs its BaseDelta bits (an 8-bit header, a 46-bit base, and each further
miss as a delta from the base in 1, 2 or 4 bytes, the least that holds
them all, or raw at 46 bits) and 12 index bytes.  The recording space
holds half the application's input bytes; a table that would overflow it
keeps the prefix of entries that fits.  At the next epoch the recording
becomes the replay space.  Replay of iteration ``k`` reads the table of
iteration ``k`` of the previous epoch: each target read matches every
entry recorded for its vertex, in recorded order, up to 24 KiB of entry
bytes per read (bits // 8), and issues their misses at the position of the
target read ``ceil(lookahead_accesses / mean gap between the iteration's
target reads)`` reads earlier (the iteration's first, near its start).
Metadata bytes: per replayed table with at least one target read, 12
index bytes per entry, and when a read matched, the matched entries'
bytes read and written back; per recorded table, its bytes written.

Where the simulator departs from its own docstrings, the forms here
follow the simulator (each is listed in ``PERF.md``): the target filter
also drops the line just past the target array; the line filter above
compares with the previous candidate, not the previous issue; the
recording space is half the input bytes, not the 20% of the storage
module's docstring.  Of identical (line, position) candidates, VLDP here
keeps the first in candidate order; the simulator keeps the one that an
unstable sort puts first, so its order among the prefetches of one
position is not defined.  Those prefetches lie in one page, so in
different L2 and LLC sets, and their order moves no row; ``check.py``
compares VLDP's streams in (position, line) order, and AMC's in the
stable position order that scoring applies.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import numpy as np

# ------------------------------------------------------------------ inputs


@dataclasses.dataclass
class Inputs:
    """What a generator reads of one workload, all of it the reference's."""

    l2_pos: np.ndarray  # trace positions of the L2 accesses (the L1 misses)
    l2_blocks: np.ndarray
    l2_miss: np.ndarray  # each one a demand miss beside next-line
    l2_iter: np.ndarray  # its iteration
    target_pos: np.ndarray  # trace positions of the target reads
    target_vid: np.ndarray  # their vertices
    target_iter: np.ndarray
    target_range: tuple  # the target array's (base address, bytes)
    iter_epochs: list  # per iteration: (epoch, index within the epoch)
    input_bytes: int

    @classmethod
    def of(cls, trace: dict, d, baseline: dict) -> "Inputs":
        """From a reference trace, its ``Demand`` and next-line outcome."""
        iter_id = trace["iter_id"]
        return cls(
            l2_pos=d.l2_pos,
            l2_blocks=d.l2_blocks,
            l2_miss=~baseline["demand_l2_hit"],
            l2_iter=iter_id[d.l2_pos],
            target_pos=trace["target_pos"],
            target_vid=trace["target_vid"],
            target_iter=iter_id[trace["target_pos"]],
            target_range=trace["target_range"],
            iter_epochs=trace["iter_epochs"],
            input_bytes=trace["input_bytes"],
        )

    @property
    def l2_epoch(self) -> np.ndarray:
        epochs = np.array([e for e, _ in self.iter_epochs], dtype=np.int64)
        return epochs[self.l2_iter]


# ------------------------------------------------------------------ VLDP

PAGE_LINES = 64  # a 4-KiB page of 64-byte lines
NONE = -128  # no table entry; a delta lies in [-63, 63]
VLDP_DEGREE = 4
VLDP_WINDOW = 1500


def _key(*deltas: np.ndarray) -> np.ndarray:
    """A history of deltas as one index, the oldest first."""
    key = np.zeros(len(deltas[0]), dtype=np.int64)
    for d in deltas:
        key = key * 128 + (d + 64)
    return key


@dataclasses.dataclass
class _Pages:
    """One epoch's L2 accesses, grouped by page in stream order."""

    page: np.ndarray
    off: np.ndarray
    pos: np.ndarray
    miss: np.ndarray
    rank: np.ndarray  # index of the access within its page
    deltas: tuple  # (d, d1, d2): this delta and the two before (0 where none)
    last: np.ndarray  # the page's last access

    @classmethod
    def of(cls, blocks, pos, miss) -> "_Pages":
        order = np.argsort(blocks // PAGE_LINES, kind="stable")
        page, off = blocks[order] // PAGE_LINES, blocks[order] % PAGE_LINES
        first = np.ones(len(page), dtype=bool)
        first[1:] = page[1:] != page[:-1]
        starts = np.flatnonzero(first)
        rank = np.arange(len(page)) - np.repeat(starts, np.diff(np.append(starts, len(page))))
        d = np.zeros(len(page), dtype=np.int64)
        d[1:] = off[1:] - off[:-1]
        d[rank < 1] = 0
        d1, d2 = np.zeros_like(d), np.zeros_like(d)
        d1[1:], d2[2:] = d[:-1], d[:-2]
        d1[rank < 2], d2[rank < 3] = 0, 0
        last = np.ones(len(page), dtype=bool)
        last[:-1] = first[1:]
        return cls(page, off, pos[order], miss[order], rank, (d, d1, d2), last)


def _majority(keys: np.ndarray, nexts: np.ndarray, size: int) -> np.ndarray:
    """A dense table: each key's most frequent next value (a tie: the
    largest), ``NONE`` for a key never seen."""
    table = np.full(size, NONE, dtype=np.int64)
    if len(keys) == 0:
        return table
    pair, count = np.unique(keys * 128 + (nexts + 64), return_counts=True)
    key, nxt = pair // 128, pair % 128 - 64
    order = np.lexsort((nxt, count, key))
    key, nxt = key[order], nxt[order]
    best = np.ones(len(key), dtype=bool)
    best[:-1] = key[1:] != key[:-1]
    table[key[best]] = nxt[best]
    return table


def _vldp_train(p: _Pages) -> dict:
    """The four tables from one epoch's pages."""
    d, d1, d2 = p.deltas
    nxt = np.zeros(len(d), dtype=np.int64)
    nxt[:-1] = d[1:]
    has_next = ~p.last
    t1 = has_next & (p.rank >= 1)
    t2 = has_next & (p.rank >= 2)
    t3 = has_next & (p.rank >= 3)
    opt = has_next & (p.rank == 0)
    return dict(
        dpt1=_majority(_key(d[t1]), nxt[t1], 128),
        dpt2=_majority(_key(d1[t2], d[t2]), nxt[t2], 128**2),
        dpt3=_majority(_key(d2[t3], d1[t3], d[t3]), nxt[t3], 128**3),
        opt=_majority(p.off[opt], nxt[opt], PAGE_LINES),
    )


def _vldp_candidates(p: _Pages, tables: dict) -> tuple:
    """Each trigger's up to four lines, all first lines, then all second
    lines and so on, each in page order."""
    d, d1, d2 = p.deltas
    pred = np.where(p.rank >= 1, tables["dpt1"][_key(d)], NONE)
    dpt2 = np.where(p.rank >= 2, tables["dpt2"][_key(d1, d)], NONE)
    pred = np.where(dpt2 != NONE, dpt2, pred)
    dpt3 = np.where(p.rank >= 3, tables["dpt3"][_key(d2, d1, d)], NONE)
    pred = np.where(dpt3 != NONE, dpt3, pred)
    pred = np.where(p.rank == 0, tables["opt"][p.off], pred)
    go = np.flatnonzero(p.miss & (pred != NONE))
    off, delta = p.off[go], pred[go]
    blocks, pos = [], []
    for _ in range(VLDP_DEGREE):
        line = off + delta
        inside = (line >= 0) & (line < PAGE_LINES)
        go, off, delta = go[inside], line[inside], delta[inside]
        blocks.append(p.page[go] * PAGE_LINES + off)
        pos.append(p.pos[go])
        delta = tables["dpt1"][delta + 64]
        more = delta != NONE
        go, off, delta = go[more], off[more], delta[more]
    return np.concatenate(blocks), np.concatenate(pos)


def _window_filter(blocks: np.ndarray, pos: np.ndarray, window: int) -> np.ndarray:
    """Keep a candidate unless the previous candidate of its line, in
    (line, position, candidate) order, lies at most ``window`` earlier."""
    order = np.argsort(blocks * (1 << 32) + pos, kind="stable")  # positions < 2**32
    b, p = blocks[order], pos[order]
    keep = np.ones(len(b), dtype=bool)
    keep[1:] = (b[1:] != b[:-1]) | (p[1:] - p[:-1] > window)
    out = np.empty(len(b), dtype=bool)
    out[order] = keep
    return out


def vldp(x: Inputs) -> tuple:
    """VLDP's stream, as the module's docstring sets it out."""
    epoch = x.l2_epoch
    out_b, out_p = [], []
    tables = None
    for e in np.unique(epoch):
        sel = epoch == e
        pages = _Pages.of(x.l2_blocks[sel], x.l2_pos[sel], x.l2_miss[sel])
        if tables is not None:
            b, p = _vldp_candidates(pages, tables)
            keep = _window_filter(b, p, VLDP_WINDOW)
            out_b.append(b[keep])
            out_p.append(p[keep])
        tables = _vldp_train(pages)
    return _joined(out_b, out_p) + (0,)


# ------------------------------------------------------------------ AMC

AMC_CACHE_BYTES = 24 * 1024  # entry bytes held per target read
STORAGE_FRACTION = 0.5  # of the input bytes, per metadata space
INDEX_ENTRY_BYTES = 12
HEADER_BITS, BASE_BITS = 8, 46


@dataclasses.dataclass
class _Table:
    """One iteration's recorded entries; entry ``i``'s misses are
    ``blocks[start[i]:start[i + 1]]``."""

    trigger: np.ndarray  # the target vertex of each entry
    bits: np.ndarray
    start: np.ndarray
    blocks: np.ndarray

    @property
    def entries(self) -> int:
        return len(self.trigger)

    @property
    def nbytes(self) -> int:
        return (int(self.bits.sum()) + 7) // 8 + INDEX_ENTRY_BYTES * self.entries

    def prefix(self, budget: int) -> "_Table":
        """The leading entries whose bytes, each rounded up, fit ``budget``."""
        cost = np.cumsum((self.bits + 7) // 8 + INDEX_ENTRY_BYTES)
        k = int(np.searchsorted(cost, budget, side="right"))
        return _Table(self.trigger[:k], self.bits[:k], self.start[: k + 1],
                      self.blocks[: self.start[k]])


def _ranks(counts: np.ndarray) -> np.ndarray:
    """0 .. c - 1 for each count ``c``, concatenated."""
    ends = np.cumsum(counts)
    return np.arange(int(ends[-1]) if len(ends) else 0) - np.repeat(ends - counts, counts)


def _record(tpos, tvid, mpos, mblocks, per_entry: int) -> _Table:
    owner = np.searchsorted(tpos, mpos, side="right") - 1
    after = owner >= 0
    owner, blocks = owner[after], mblocks[after]
    if len(owner) == 0:
        z = np.zeros(0, dtype=np.int64)
        return _Table(z, z, np.zeros(1, dtype=np.int64), blocks)
    new = np.ones(len(owner), dtype=bool)
    new[1:] = owner[1:] != owner[:-1]
    run = np.flatnonzero(new)
    rank = _ranks(np.diff(np.append(run, len(owner))))
    first = np.flatnonzero(rank % per_entry == 0)
    n = np.diff(np.append(first, len(owner)))
    spread = np.maximum.reduceat(np.abs(blocks - np.repeat(blocks[first], n)), first)
    width = np.select([spread < 2**7, spread < 2**15, spread < 2**31], [1, 2, 4], 0)
    bits = np.where(
        width > 0,
        HEADER_BITS + BASE_BITS + (n - 1) * 8 * width,
        HEADER_BITS + BASE_BITS * n,
    )
    return _Table(tvid[owner[first]], bits, np.append(first, len(owner)), blocks)


def _replay(table: _Table, tpos, tvid, lookahead: int) -> tuple:
    """The misses issued, and the metadata bytes read and written."""
    gap = (tpos[-1] - tpos[0]) / (len(tpos) - 1) if len(tpos) > 1 else 1.0
    ahead = max(math.ceil(lookahead / max(gap, 1.0)), 1)
    at = tpos[np.maximum(np.arange(len(tpos)) - ahead, 0)]
    index_bytes = INDEX_ENTRY_BYTES * table.entries
    order = np.argsort(table.trigger, kind="stable")
    lo = np.searchsorted(table.trigger[order], tvid, side="left")
    count = np.searchsorted(table.trigger[order], tvid, side="right") - lo
    if not count.any():
        return None, index_bytes, 0
    owner = np.repeat(np.arange(len(tvid)), count)
    entry = order[np.repeat(lo, count) + _ranks(count)]
    ebytes = table.bits[entry] // 8
    cum = np.cumsum(ebytes)
    first = (np.cumsum(count) - count)[owner]  # each read's first entry
    fits = cum - cum[first] + ebytes[first] <= AMC_CACHE_BYTES
    entry, owner = entry[fits], owner[fits]
    n = table.start[entry + 1] - table.start[entry]
    blocks = table.blocks[np.repeat(table.start[entry], n) + _ranks(n)]
    matched = int(ebytes[fits].sum())
    return (blocks, np.repeat(at[owner], n)), index_bytes + matched, matched


def amc(x: Inputs, max_misses_per_entry: int = 20, lookahead_accesses: int = 90) -> tuple:
    """AMC's stream and metadata bytes, as the module's docstring sets it out."""
    capacity = int(STORAGE_FRACTION * x.input_bytes)
    base, size = x.target_range
    lo, hi = base // 64, (base + size) // 64
    miss = x.l2_miss & ~((x.l2_blocks >= lo) & (x.l2_blocks <= hi))
    mpos, mblocks, miter = x.l2_pos[miss], x.l2_blocks[miss], x.l2_iter[miss]
    iters = np.arange(len(x.iter_epochs) + 1)
    tb, mb = np.searchsorted(x.target_iter, iters), np.searchsorted(miter, iters)
    replaying, recording = {}, {}
    read = written = 0
    out_b, out_p = [], []
    current = None
    for it, (epoch, within) in enumerate(x.iter_epochs):
        if epoch != current:
            if current is not None:
                replaying, recording = recording, {}
            current = epoch
        tpos = x.target_pos[tb[it]: tb[it + 1]]
        tvid = x.target_vid[tb[it]: tb[it + 1]]
        if len(tpos) == 0:
            continue
        table = replaying.get(within)
        if table is not None and table.entries:
            issued, r, w = _replay(table, tpos, tvid, lookahead_accesses)
            read, written = read + r, written + w
            if issued is not None:
                out_b.append(issued[0])
                out_p.append(issued[1])
        table = _record(tpos, tvid, mpos[mb[it]: mb[it + 1]],
                        mblocks[mb[it]: mb[it + 1]], max_misses_per_entry)
        used = sum(t.nbytes for t in recording.values())
        if used + table.nbytes > capacity:
            table = table.prefix(max(capacity - used, 0))
        recording[within] = table
        written += table.nbytes
    return _joined(out_b, out_p) + (read + written,)


# ------------------------------------------------------------------ registry

@dataclasses.dataclass(frozen=True)
class Generator:
    fn: Callable
    overrides: frozenset  # the overrides it takes
    ordered: bool  # whether the simulator's order within a position is defined


GENERATORS = {
    "amc": Generator(amc, frozenset({"max_misses_per_entry", "lookahead_accesses"}), True),
    "vldp": Generator(vldp, frozenset(), False),
}


def can_generate(entry: dict) -> bool:
    """Whether a prefetcher entry of a configuration or traffic has a plain
    generator here, with every override it names."""
    known = GENERATORS.get(entry["registry"])
    return known is not None and set(entry.get("overrides", {})) <= known.overrides


def generate(entry: dict, x: Inputs) -> tuple:
    """The stream of a prefetcher entry (``registry``, ``overrides``)."""
    return GENERATORS[entry["registry"]].fn(x, **entry.get("overrides", {}))


def _joined(blocks: list, pos: list) -> tuple:
    if not blocks:
        return np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64)
    return np.concatenate(blocks).astype(np.int64), np.concatenate(pos).astype(np.int64)
