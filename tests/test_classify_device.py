"""The jitted chain-classification program (``repro.memsim.classify_device``)
against the numpy classification, "no future demand" and unmerge it
replaces on a TPU, field by field; and the choice between the two paths."""
import dataclasses
from unittest import mock

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare environment: seeded stub strategies
    from _hypothesis_fallback import given, settings, st

from repro.core import obs
from repro.memsim import classify_device, hierarchy
from repro.memsim.config import CacheLevelConfig, HierarchyConfig
from repro.memsim.engine import cache_pass, use_engine
from repro.memsim.hierarchy import (
    DemandProfile,
    PrefetchOutcome,
    _finish_prefetch_outcome,
    _merge_prefetch_stream,
    _no_future_demand,
)

# Small enough that short random streams both hit and miss at every level.
TINY = HierarchyConfig(
    l1=CacheLevelConfig(4 * 64, 2, 4, 8),
    l2=CacheLevelConfig(8 * 64, 2, 12, 16),
    llc=CacheLevelConfig(32 * 64, 4, 42, 128),
    dram_latency=170,
    pf_fill_window=3,
    name="tiny",
)


def _profile(rng, nd, span, cfg=TINY, pos_base=0):
    """A baseline profile whose L2 substream has exactly ``nd`` events."""
    n_acc = 2 * nd + 1
    l2_idx = np.sort(rng.choice(n_acc, nd, replace=False))
    blocks = rng.integers(0, span, n_acc).astype(np.int64)
    l1_hit = np.ones(n_acc, dtype=bool)
    l1_hit[l2_idx] = False
    l2_blocks = blocks[l2_idx]
    l2_hit = cache_pass(l2_blocks, cfg.l2.sets, cfg.l2.ways)
    iters = np.zeros(n_acc, dtype=np.int32)
    return DemandProfile(
        blocks=blocks,
        iter_id=iters,
        l1_hit=l1_hit,
        l2_pos=l2_idx.astype(np.int64) + pos_base,
        l2_blocks=l2_blocks,
        l2_iter=iters[l2_idx],
        l2_hit=l2_hit,
        llc_hit=cache_pass(l2_blocks[~l2_hit], cfg.llc.sets, cfg.llc.ways),
        cfg=cfg,
    )


def _prefetches(rng, profile, npf, span, issuers):
    """Prefetches at trace positions (some at a demand's own position),
    half of them to blocks the demand stream touches."""
    lo = int(profile.l2_pos[0])
    hi = int(profile.l2_pos[-1]) + 2
    pos = np.sort(rng.integers(lo, hi, npf)).astype(np.int64)
    on_demand = rng.random(npf) < 0.3
    pos[on_demand] = rng.choice(profile.l2_pos, int(on_demand.sum()))
    pos.sort()
    blocks = np.where(
        rng.random(npf) < 0.5,
        rng.choice(profile.l2_blocks, npf),
        rng.integers(0, span, npf),
    ).astype(np.int64)
    issuer = rng.integers(0, issuers, npf).astype(np.int8)
    return blocks, pos, issuer


def _scored(profile, pf_blocks, pf_pos, pf_issuer):
    cfg = profile.cfg
    merged = _merge_prefetch_stream(profile, pf_blocks, pf_pos, pf_issuer)
    hit = cache_pass(merged["mblocks_s"], cfg.l2.sets, cfg.l2.ways)
    llc_hit = cache_pass(merged["mblocks_s"][~hit], cfg.llc.sets, cfg.llc.ways)
    return merged, hit, llc_hit


def _assert_same(got: PrefetchOutcome, want: PrefetchOutcome):
    for f in dataclasses.fields(PrefetchOutcome):
        a, b = getattr(got, f.name), getattr(want, f.name)
        if isinstance(b, np.ndarray):
            assert a.dtype == b.dtype, f.name
            np.testing.assert_array_equal(a, b, err_msg=f.name)
        else:
            assert a == b, f.name


def _outcome(profile, scored, device, keep_llc_stream=False):
    """``_finish_prefetch_outcome`` with the device program or numpy,
    checking through the counter that the program ran exactly when asked."""
    with mock.patch.object(
        hierarchy, "_classify_on_device", lambda: device
    ), obs.metrics_registry() as reg:
        out = _finish_prefetch_outcome(profile, *scored, 123, keep_llc_stream)
    n_events = len(scored[0]["mblocks_s"]) if device else 0
    assert reg.counter("prefetch.classify_device") == n_events
    return out


def _both(profile, scored, keep_llc_stream=False):
    return (
        _outcome(profile, scored, True, keep_llc_stream),
        _outcome(profile, scored, False, keep_llc_stream),
    )


@given(
    total=st.sampled_from([127, 128, 129, 255, 256, 257, 1023, 1024, 1025]),
    pf_frac=st.floats(0.05, 0.8),
    span=st.sampled_from([6, 24, 5000]),  # 5000: mostly one-event chains
    issuers=st.sampled_from([1, 3]),
    fill_window=st.sampled_from([0, 3, 40]),
    keep=st.sampled_from([False, True]),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=30, deadline=None)
def test_device_program_matches_numpy(
    total, pf_frac, span, issuers, fill_window, keep, seed
):
    rng = np.random.default_rng(seed)
    npf = min(total - 1, max(1, int(total * pf_frac)))
    cfg = dataclasses.replace(TINY, pf_fill_window=fill_window)
    profile = _profile(rng, total - npf, span, cfg)
    scored = _scored(profile, *_prefetches(rng, profile, npf, span, issuers))
    assert len(scored[0]["mblocks_s"]) == total
    got, want = _both(profile, scored, keep_llc_stream=keep)
    _assert_same(got, want)


@pytest.mark.parametrize("keep", [False, True])
def test_device_program_one_prefetch(keep):
    rng = np.random.default_rng(3)
    profile = _profile(rng, 200, 20)
    # A prefetch of a block the next demands reuse: useful (or late).
    p = int(profile.l2_pos[50])
    scored = _scored(
        profile,
        np.array([profile.l2_blocks[60]]),
        np.array([p]),
        np.array([2], dtype=np.int8),
    )
    got, want = _both(profile, scored, keep_llc_stream=keep)
    _assert_same(got, want)
    assert got.issued == 1


def test_device_program_scores_a_real_stream():
    """A next-line-style stream over a skewed trace: every class occurs."""
    rng = np.random.default_rng(11)
    profile = _profile(rng, 3000, 400)
    pf_pos = profile.l2_pos[::2]
    pf_blocks = profile.l2_blocks[::2] + 1
    issuer = (np.arange(len(pf_pos)) % 2).astype(np.int8)
    got, want = _both(profile, _scored(profile, pf_blocks, pf_pos, issuer))
    _assert_same(got, want)
    for name in ("demand_useful", "demand_late", "pf_redundant", "pf_early",
                 "pf_no_future"):
        assert getattr(got, name).any(), name
    assert set(np.unique(got.demand_fill_issuer)) == {-1, 0, 1}


def _brute_no_future(pf_blocks, pf_pos, d_blocks, d_pos):
    return np.array(
        [not np.any((d_blocks == b) & (d_pos > p)) for b, p in zip(pf_blocks, pf_pos)],
        dtype=bool,
    )


@given(
    nd=st.integers(1, 300),
    npf=st.integers(1, 300),
    span=st.integers(1, 40),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=30, deadline=None)
def test_chain_no_future_matches_searchsorted_and_brute_force(nd, npf, span, seed):
    rng = np.random.default_rng(seed)
    profile = _profile(rng, nd, span)
    # Prefetches at demand positions (2p+1 against the demand's 2p) and
    # at positions no demand has.
    pf_pos = np.sort(
        np.where(
            rng.random(npf) < 0.5,
            rng.choice(profile.l2_pos, npf),
            rng.integers(0, 2 * nd + 2, npf),
        )
    ).astype(np.int64)
    pf_blocks = rng.integers(0, span, npf).astype(np.int64)
    scored = _scored(profile, pf_blocks, pf_pos, None)
    merged = scored[0]
    got = _outcome(profile, scored, True).pf_no_future
    want = _no_future_demand(
        merged["pf_blocks"], merged["pf_pos"],
        profile.l2_miss_blocks, profile.l2_miss_pos,
    )
    brute = _brute_no_future(
        merged["pf_blocks"], merged["pf_pos"],
        profile.l2_miss_blocks, profile.l2_miss_pos,
    )
    np.testing.assert_array_equal(want, brute)
    np.testing.assert_array_equal(got, brute)


def test_no_future_is_strict_at_the_same_position():
    """A demand at the prefetch's own trigger position is in its past."""
    rng = np.random.default_rng(5)
    profile = _profile(rng, 40, 10_000)  # distinct blocks: all baseline misses
    k = 17
    b, p = profile.l2_blocks[k : k + 1], profile.l2_pos[k : k + 1]
    got = _outcome(profile, _scored(profile, b, p, None), True)
    assert got.pf_no_future.tolist() == [True]
    got = _outcome(profile, _scored(profile, b, p - 1, None), True)
    assert got.pf_no_future.tolist() == [False]


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the backend query answer ``tpu`` (the program itself still
    runs on the CPU backend, which it compiles for like any other)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _one_scored_stream(pos_base=0):
    rng = np.random.default_rng(21)
    profile = _profile(rng, 500, 60, pos_base=pos_base)
    return profile, _scored(profile, *_prefetches(rng, profile, 200, 60, 2))


def _classify_counting(profile, scored):
    with obs.metrics_registry() as reg:
        out = _finish_prefetch_outcome(profile, *scored, 123, False)
    return out, reg.counter("prefetch.classify_device")


def test_path_follows_backend_and_engine(on_tpu):
    with use_engine("fused"):
        assert hierarchy._classify_on_device()
    with use_engine("pallas"):
        assert hierarchy._classify_on_device()
    with use_engine("reference"):
        assert not hierarchy._classify_on_device()


def test_cpu_backend_classifies_on_host():
    assert jax.default_backend() == "cpu"
    profile, scored = _one_scored_stream()
    with use_engine("fused"):
        assert not hierarchy._classify_on_device()
        _, counted = _classify_counting(profile, scored)
    assert counted == 0


@pytest.mark.parametrize("engine", ["fused", "reference"])
def test_counter_counts_events_classified_on_device(on_tpu, engine):
    profile, scored = _one_scored_stream()
    with use_engine(engine):
        out, counted = _classify_counting(profile, scored)
    _assert_same(out, _outcome(profile, scored, False))
    n_events = len(scored[0]["mblocks_s"])
    assert counted == (n_events if engine == "fused" else 0)


def test_positions_past_int32_stay_on_host(on_tpu):
    """Doubled positions that would not fit int32 take the numpy path."""
    profile, scored = _one_scored_stream(pos_base=2**30)
    assert not classify_device.fits_int32(
        int(scored[0]["mblocks_s"].max()), int(scored[0]["mpos_s"][-1]), 0
    )
    with use_engine("fused"):
        out, counted = _classify_counting(profile, scored)
    assert counted == 0
    _assert_same(out, _outcome(profile, scored, False))
