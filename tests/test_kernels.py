"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode — TPU is the target, CPU executes the kernel body)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare environment: seeded stub strategies
    from _hypothesis_fallback import given, settings, st

from repro.kernels.amc_gather.amc_gather import (
    amc_gather,
    amc_gather_segment_sum,
)
from repro.kernels.amc_gather.ref import gather_ref, gather_segment_sum_ref
from repro.kernels.basedelta.basedelta import (
    basedelta_compress_tiles,
    basedelta_decompress_tiles,
)
from repro.kernels.basedelta.ops import roundtrip
from repro.kernels.basedelta.ref import compress_ref, decompress_ref
from repro.kernels.cache_sim.cache_sim import lru_hits
from repro.kernels.cache_sim.ops import cache_pass_pallas
from repro.kernels.cache_sim.ref import lru_hits_ref
from repro.kernels.flash_attn.ops import mha
from repro.kernels.flash_attn.ref import attention_ref
from repro.kernels.ssd_scan.ref import ssd_naive
from repro.kernels.ssd_scan.ssd_scan import ssd_scan


# --------------------------- flash_attn ---------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,kv,hd,causal,win",
    [
        (2, 256, 4, 2, 64, True, 0),
        (1, 384, 2, 2, 128, True, 128),
        (2, 200, 4, 4, 64, False, 0),
        (1, 130, 2, 1, 64, True, 0),  # ragged tail block
    ],
)
def test_flash_attn_vs_oracle(b, s, h, kv, hd, causal, win, dtype):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, h, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, hd), dtype)
    out = mha(q, k, v, causal=causal, sliding_window=win, interpret=True)
    groups = h // kv
    kr = jnp.repeat(k, groups, axis=2)
    vr = jnp.repeat(v, groups, axis=2)
    ref = attention_ref(
        jnp.moveaxis(q, 2, 1).reshape(b * h, s, hd),
        jnp.moveaxis(kr, 2, 1).reshape(b * h, s, hd),
        jnp.moveaxis(vr, 2, 1).reshape(b * h, s, hd),
        causal=causal,
        sliding_window=win,
    )
    ref = jnp.moveaxis(ref.reshape(b, h, s, hd), 1, 2)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


# --------------------------- amc_gather ---------------------------


@given(
    v=st.integers(8, 128),
    d=st.sampled_from([8, 128]),
    n=st.integers(1, 64),
    seed=st.integers(0, 20),
)
@settings(max_examples=12, deadline=None)
def test_amc_gather_vs_oracle(v, d, n, seed):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.normal(size=(v, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, v, n), jnp.int32)
    out = amc_gather(table, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(gather_ref(table, idx)))


def test_amc_gather_segment_sum_vs_oracle():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    n, nseg = 50, 8
    idx = jnp.asarray(rng.integers(0, 64, n), jnp.int32)
    # every segment non-empty (kernel writes only flushed segments)
    segs = np.sort(np.concatenate([np.arange(nseg), rng.integers(0, nseg, n - nseg)]))
    segs = jnp.asarray(segs, jnp.int32)
    out = amc_gather_segment_sum(table, idx, segs, nseg, interpret=True)
    ref = gather_segment_sum_ref(table, idx, segs, nseg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


def test_amc_gather_session_replay():
    from repro.kernels.amc_gather.ops import AMCGatherSession

    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    idx1 = rng.integers(0, 32, 20)
    idx2 = idx1.copy()
    idx2[[3, 7]] = (idx2[[3, 7]] + 5) % 32  # 10% churn, like the graphs
    sess = AMCGatherSession(interpret=True)
    sess.gather(table, jnp.asarray(idx1, jnp.int32))
    sess.update()
    out2 = sess.gather(table, jnp.asarray(idx2, jnp.int32))
    np.testing.assert_allclose(np.asarray(out2), np.asarray(table[idx2]), rtol=1e-6)
    assert sess.stats["replayed"] == 1


# --------------------------- basedelta ---------------------------


@given(
    e=st.integers(1, 30),
    width=st.sampled_from([8, 32]),
    spread=st.sampled_from([50, 5000, 10**6]),
    seed=st.integers(0, 30),
)
@settings(max_examples=15, deadline=None)
def test_basedelta_tiles_vs_ref(e, width, spread, seed):
    rng = np.random.default_rng(seed)
    counts = rng.integers(1, width + 1, e).astype(np.int32)
    tiles = np.zeros((e, width), np.int32)
    for i in range(e):
        base = rng.integers(0, 2**24)
        tiles[i, : counts[i]] = base + rng.integers(-spread, spread, counts[i])
    d_k, m_k = basedelta_compress_tiles(
        jnp.asarray(tiles), jnp.asarray(counts), interpret=True
    )
    d_r, m_r = compress_ref(jnp.asarray(tiles), jnp.asarray(counts))
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m_r))
    # decompress roundtrip
    rec = basedelta_decompress_tiles(
        jnp.asarray(tiles[:, 0]), d_k, interpret=True
    )
    ref = decompress_ref(jnp.asarray(tiles[:, 0]), d_r)
    np.testing.assert_array_equal(np.asarray(rec), np.asarray(ref))


def test_basedelta_ragged_roundtrip():
    rng = np.random.default_rng(2)
    mb = rng.integers(1 << 20, (1 << 20) + 4000, 300).astype(np.int64)
    # AMC invariant: entries are split at <=20 misses (paper Fig 16)
    sizes = rng.integers(1, 21, 40)
    off = np.concatenate([[0], np.cumsum(sizes)])
    off = off[off <= 300]
    if off[-1] != 300:
        off = np.append(off, 300)
    rec = roundtrip(mb, off)
    np.testing.assert_array_equal(rec, mb)


def test_pack_ragged_rejects_oversized_entries():
    with pytest.raises(AssertionError):
        roundtrip(np.arange(100, dtype=np.int64), np.array([0, 50, 100]))


# --------------------------- cache_sim ---------------------------


@given(
    sets=st.sampled_from([2, 8]),
    ways=st.sampled_from([1, 2, 4]),
    n=st.integers(1, 200),
    span=st.integers(1, 60),
    seed=st.integers(0, 20),
)
@settings(max_examples=12, deadline=None)
def test_cache_sim_kernel_vs_oracle(sets, ways, n, span, seed):
    from repro.memsim.engine import group_by_set

    rng = np.random.default_rng(seed)
    blocks = rng.integers(0, span, n).astype(np.int64)
    padded, _, _, _ = group_by_set(blocks, sets)  # (L, sets)
    got = np.asarray(lru_hits(jnp.asarray(padded), ways, set_tile=sets, interpret=True))
    ref = lru_hits_ref(np.ascontiguousarray(padded.T), ways).T
    real = padded >= 0  # oracle skips tail pads; the kernel runs over them
    np.testing.assert_array_equal(got[real], ref[real])


@pytest.mark.parametrize("kernel", ["lru", "fused"])
def test_cache_kernels_carry_across_time_chunks(kernel):
    """A resumed pass whose substreams span several VMEM time chunks is
    bit-identical to one uninterrupted reference pass: hit results and the
    canonical carried states (ages keep increasing across chunks)."""
    from repro.kernels.cache_sim.cache_sim import TIME_CHUNK
    from repro.memsim.engine import group_by_set
    from repro.memsim.fused import fused_cache_pass
    from repro.memsim.scan_cache import cache_pass as cache_pass_reference

    rng = np.random.default_rng(11)
    blocks = rng.integers(0, 300, 12_000).astype(np.int64)
    h = 5_000
    sets, ways = 4, 2
    assert group_by_set(blocks[h:], sets)[0].shape[0] > TIME_CHUNK
    if kernel == "lru":
        ref, ref_st = cache_pass_reference(blocks, sets, ways, None, True)
        first, st = cache_pass_pallas(
            blocks[:h], sets, ways, interpret=True, return_state=True
        )
        second, st = cache_pass_pallas(
            blocks[h:], sets, ways, interpret=True, state=st, return_state=True
        )
        ref_sts, got_sts = [ref_st], [st]
    else:
        geom = ((sets, ways), (2 * sets, ways), (8 * sets, 2 * ways))
        ref, ref_sts = fused_cache_pass(
            blocks, geom, return_states=True, use_pallas=False, force_scan=True
        )
        first, sts = fused_cache_pass(
            blocks[:h], geom, return_states=True, use_pallas=True
        )
        second, got_sts = fused_cache_pass(
            blocks[h:], geom, states=sts, return_states=True, use_pallas=True
        )
    np.testing.assert_array_equal(np.concatenate([first, second]), ref)
    for got, want in zip(got_sts, ref_sts):
        np.testing.assert_array_equal(got.tags, want.tags)
        np.testing.assert_array_equal(got.age, want.age)


def test_cache_sim_full_stream_matches_reference_engine():
    from repro.memsim.scan_cache import cache_pass as cache_pass_reference

    rng = np.random.default_rng(5)
    blocks = rng.integers(0, 700, 2_000).astype(np.int64)
    out = cache_pass_pallas(blocks, 16, 4, set_tile=4, interpret=True)
    np.testing.assert_array_equal(out, cache_pass_reference(blocks, 16, 4))


# --------------------------- ssd_scan ---------------------------


@given(
    s=st.integers(8, 120),
    p=st.sampled_from([8, 32]),
    n=st.sampled_from([4, 16]),
    chunk=st.sampled_from([16, 32]),
    seed=st.integers(0, 20),
)
@settings(max_examples=10, deadline=None)
def test_ssd_kernel_vs_naive(s, p, n, chunk, seed):
    rng = np.random.default_rng(seed)
    bh = 2
    x = jnp.asarray(rng.normal(size=(bh, s, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.1, 0.9, (bh, s)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.3, 2.0, bh), jnp.float32)
    b = jnp.asarray(rng.normal(size=(bh, s, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(bh, s, n)), jnp.float32)
    out = ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=True)
    ref = ssd_naive(np.asarray(x), np.asarray(dt), np.asarray(a), np.asarray(b), np.asarray(c))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)
