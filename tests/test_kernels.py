"""Per-kernel correctness sweeps: Pallas (interpret=True) vs ref.py oracles.

Shapes/dtypes swept per the deliverable: every kernel is exercised across
block-divisible and ragged shapes, GQA group sizes, fp32/bf16, and the
masking variants (causal / sliding-window / partial cache fill).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as fa_pallas
from repro.kernels.decode_attention import (
    _pick_block_s,
    _ragged_block_index,
    decode_attention as da_pallas,
    decode_attention_quant as daq_pallas,
    paged_decode_attention as pda_pallas,
)
from repro.kernels.sampling import fused_sample as fs_pallas
from repro.kernels.ssd import ssd as ssd_pallas
from repro.kernels.rmsnorm import rmsnorm as rn_pallas

jax.config.update("jax_default_matmul_precision", "highest")


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("S,H,KVH,D,block", [
    (128, 4, 4, 32, 64),    # MHA
    (256, 4, 2, 64, 64),    # GQA group 2
    (256, 8, 1, 32, 128),   # MQA
    (192, 4, 4, 64, 64),    # ragged seq vs block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 96])
def test_flash_attention(S, H, KVH, D, block, dtype, window):
    if S % block != 0:
        pytest.skip("pallas path requires block-divisible seq (wrapper asserts)")
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    B = 2
    q = jax.random.normal(k[0], (B, S, H, D), dtype)
    kk = jax.random.normal(k[1], (B, S, KVH, D), dtype)
    vv = jax.random.normal(k[2], (B, S, KVH, D), dtype)
    o_ref = ref.flash_attention(q, kk, vv, causal=True, window=window)
    o_pal = fa_pallas(q, kk, vv, causal=True, window=window,
                      block_q=block, block_k=block, interpret=True)
    np.testing.assert_allclose(np.array(o_pal, np.float32),
                               np.array(o_ref, np.float32), **tol(dtype))


def test_flash_attention_q_offset():
    """Chunked prefill: queries are a suffix of the kv sequence."""
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, H, D = 1, 128, 2, 32
    q = jax.random.normal(k[0], (B, 64, H, D))
    kk = jax.random.normal(k[1], (B, S, H, D))
    vv = jax.random.normal(k[2], (B, S, H, D))
    o_ref = ref.flash_attention(q, kk, vv, causal=True, q_offset=64)
    o_pal = fa_pallas(q, kk, vv, causal=True, q_offset=64,
                      block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.array(o_pal), np.array(o_ref), atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------- #
# decode attention
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("S,H,KVH,D,block", [
    (256, 4, 4, 32, 64),
    (512, 8, 2, 64, 128),
    (256, 16, 1, 32, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(S, H, KVH, D, block, dtype):
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    B = 3
    q = jax.random.normal(k[0], (B, H, D), dtype)
    kk = jax.random.normal(k[1], (B, S, KVH, D), dtype)
    vv = jax.random.normal(k[2], (B, S, KVH, D), dtype)
    cl = jnp.array([S // 3, S, 1], jnp.int32)  # partial / full / single-slot
    o_r, l_r = ref.decode_attention(q, kk, vv, cl, return_lse=True)
    o_p, l_p = da_pallas(q, kk, vv, cl, block_s=block, interpret=True)
    np.testing.assert_allclose(np.array(o_p, np.float32),
                               np.array(o_r, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.array(l_p), np.array(l_r), atol=1e-3, rtol=1e-3)


def test_decode_attention_sharded_combine():
    """Sequence-sharded cache: per-shard (o,lse) must combine exactly."""
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    B, S, H, KVH, D, P = 2, 256, 4, 2, 32, 4
    q = jax.random.normal(k[0], (B, H, D))
    kk = jax.random.normal(k[1], (B, S, KVH, D))
    vv = jax.random.normal(k[2], (B, S, KVH, D))
    cl = jnp.array([S - 10, S // 2], jnp.int32)
    o_full, _ = da_pallas(q, kk, vv, cl, block_s=64, interpret=True)
    shard = S // P
    os_, ls_ = [], []
    for i in range(P):
        o_i, l_i = da_pallas(q, kk[:, i * shard:(i + 1) * shard],
                             vv[:, i * shard:(i + 1) * shard], cl,
                             pos_offset=i * shard, block_s=64, interpret=True)
        os_.append(o_i)
        ls_.append(l_i)
    o_comb = ref.combine_decode_shards(jnp.stack(os_), jnp.stack(ls_))
    np.testing.assert_allclose(np.array(o_comb), np.array(o_full), atol=2e-5, rtol=2e-5)


def test_pick_block_s_largest_divisor():
    assert _pick_block_s(256, 64) == 64
    assert _pick_block_s(160, 64) == 40   # non-power-of-two arena width
    assert _pick_block_s(160, 512) == 160
    assert _pick_block_s(7, 4) == 1       # prime: falls to 1, grid still exact
    assert _pick_block_s(96, 64) == 48
    for S in (96, 160, 192, 250):
        bs = _pick_block_s(S, 64)
        assert S % bs == 0 and bs <= 64


def test_ragged_block_index_clamps():
    """Dead grid steps must repeat a live block index (so Pallas elides the
    copy) and live steps must map to themselves."""
    f = functools.partial(_ragged_block_index, block_s=64, num_blocks=4,
                          pos_offset=0, window=None)
    lens = jnp.int32(130)  # needs blocks 0..2
    got = [int(f(jnp.int32(si), lens)) for si in range(4)]
    assert got == [0, 1, 2, 2]  # step 3 re-fetches block 2: copy elided
    # kv_len=1 needs only block 0
    assert [int(f(jnp.int32(si), jnp.int32(1))) for si in range(4)] == [0] * 4
    # full cache: identity
    assert [int(f(jnp.int32(si), jnp.int32(256))) for si in range(4)] == [0, 1, 2, 3]
    # SWA clamps the head too: window=64, kv_len=256 -> live kpos 192..255,
    # exactly block 3 (first = (256-64)//64 = 3); blocks 0-2 are dead steps
    fw = functools.partial(_ragged_block_index, block_s=64, num_blocks=4,
                           pos_offset=0, window=64)
    assert [int(fw(jnp.int32(si), jnp.int32(256))) for si in range(4)] == [3] * 4
    # window=96 straddles a block boundary: live kpos 160..255 -> blocks 2..3
    fw2 = functools.partial(_ragged_block_index, block_s=64, num_blocks=4,
                            pos_offset=0, window=96)
    assert [int(fw2(jnp.int32(si), jnp.int32(256))) for si in range(4)] == [2, 2, 2, 3]
    # sharded: pos_offset shifts the live range
    fo = functools.partial(_ragged_block_index, block_s=64, num_blocks=4,
                           pos_offset=256, window=None)
    assert [int(fo(jnp.int32(si), jnp.int32(300))) for si in range(4)] == [0, 0, 0, 0]


def test_decode_attention_non_power_of_two_seq():
    """Regression: S=160 used to trip ``assert S % block_s == 0`` with the
    default block; the wrapper now auto-picks the largest divisor (40)."""
    k = jax.random.split(jax.random.PRNGKey(9), 3)
    B, S, H, KVH, D = 2, 160, 4, 2, 32
    q = jax.random.normal(k[0], (B, H, D))
    kk = jax.random.normal(k[1], (B, S, KVH, D))
    vv = jax.random.normal(k[2], (B, S, KVH, D))
    cl = jnp.array([97, 160], jnp.int32)
    o_r, l_r = ref.decode_attention(q, kk, vv, cl, return_lse=True)
    o_p, l_p = da_pallas(q, kk, vv, cl, block_s=64, interpret=True)
    np.testing.assert_allclose(np.array(o_p), np.array(o_r), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.array(l_p), np.array(l_r), atol=1e-3, rtol=1e-3)


def test_decode_attention_ragged_edges():
    """kv_len = 1 (single live slot) and kv_len = S (no dead tiles) are the
    fetch-skip clamp's boundary cases."""
    k = jax.random.split(jax.random.PRNGKey(10), 3)
    B, S, H, KVH, D = 2, 256, 4, 2, 32
    q = jax.random.normal(k[0], (B, H, D))
    kk = jax.random.normal(k[1], (B, S, KVH, D))
    vv = jax.random.normal(k[2], (B, S, KVH, D))
    cl = jnp.array([1, S], jnp.int32)
    o_r, l_r = ref.decode_attention(q, kk, vv, cl, return_lse=True)
    o_p, l_p = da_pallas(q, kk, vv, cl, block_s=64, interpret=True)
    np.testing.assert_allclose(np.array(o_p), np.array(o_r), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.array(l_p), np.array(l_r), atol=1e-3, rtol=1e-3)


def test_decode_attention_sliding_window():
    k = jax.random.split(jax.random.PRNGKey(4), 3)
    B, S, H, KVH, D = 2, 256, 4, 2, 32
    q = jax.random.normal(k[0], (B, H, D))
    kk = jax.random.normal(k[1], (B, S, KVH, D))
    vv = jax.random.normal(k[2], (B, S, KVH, D))
    cl = jnp.array([200, 256], jnp.int32)
    o_r, _ = ref.decode_attention(q, kk, vv, cl, window=64, return_lse=True)
    o_p, _ = da_pallas(q, kk, vv, cl, window=64, block_s=64, interpret=True)
    np.testing.assert_allclose(np.array(o_p), np.array(o_r), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S,lens,window", [
    (256, [1, 97, 256], None),   # ragged kv_len: one slot, a partial tile, full
    (160, [40, 159, 160], None),  # non-power-of-two arena width
    (256, [60, 200, 256], 64),    # SWA window
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_stacked_arena_matches_per_layer(S, lens, window,
                                                          dtype):
    """The decode loop's stacked lane-folded arena (N, B, S, KVH*D), read at
    layer n through the scalar-prefetched layer index, gives bitwise what
    the per-layer call gives on ``arena[n]`` — with the index traced, as
    the layer loop passes it."""
    N, B, H, KVH, D = 3, 3, 4, 2, 32
    k = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(k[0], (B, H, D), dtype)
    ka = jax.random.normal(k[1], (N, B, S, KVH * D), dtype)
    va = jax.random.normal(k[2], (N, B, S, KVH * D), dtype)
    cl = jnp.asarray(lens, jnp.int32)
    stacked = jax.jit(functools.partial(
        da_pallas, window=window, block_s=64, interpret=True))
    for n in range(N):
        o_s, l_s = stacked(q, ka, va, cl, layer=jnp.int32(n))
        o_l, l_l = da_pallas(q, ka[n].reshape(B, S, KVH, D),
                             va[n].reshape(B, S, KVH, D), cl, window=window,
                             block_s=64, interpret=True)
        np.testing.assert_array_equal(np.asarray(o_s), np.asarray(o_l))
        np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_l))
    o_r, _ = ref.decode_attention(q, ka[1].reshape(B, S, KVH, D),
                                  va[1].reshape(B, S, KVH, D), cl,
                                  window=window, return_lse=True)
    o_s, _ = stacked(q, ka, va, cl, layer=jnp.int32(1))
    np.testing.assert_allclose(np.array(o_s, np.float32),
                               np.array(o_r, np.float32), **tol(dtype))


# --------------------------------------------------------------------------- #
# paged decode attention (block-table gather through the serving page pool)
# --------------------------------------------------------------------------- #
def _paged_pool(key, B, S, KVH, D, ps, extra_pages=5, dtype=jnp.float32):
    """A contiguous cache plus the same KV scattered into a scrambled page
    pool with per-sequence block tables (plus unowned garbage pages)."""
    k1, k2, k3 = jax.random.split(key, 3)
    kk = jax.random.normal(k1, (B, S, KVH, D), dtype)
    vv = jax.random.normal(k2, (B, S, KVH, D), dtype)
    T = S // ps
    P = B * T + extra_pages
    perm = np.random.default_rng(0).permutation(P)[: B * T]
    tables = perm.reshape(B, T).astype(np.int32)
    pool_k = jax.random.normal(k3, (P, ps, KVH, D), dtype)  # garbage base
    pool_v = jax.random.normal(jax.random.fold_in(k3, 1), (P, ps, KVH, D), dtype)
    kp = kk.reshape(B * T, ps, KVH, D)
    vp = vv.reshape(B * T, ps, KVH, D)
    pool_k = pool_k.at[perm].set(kp)
    pool_v = pool_v.at[perm].set(vp)
    return kk, vv, pool_k, pool_v, jnp.asarray(tables)


@pytest.mark.parametrize("S,H,KVH,D,ps", [
    (64, 4, 4, 32, 8),     # MHA, small pages
    (128, 8, 2, 64, 16),   # GQA
    (64, 8, 1, 32, 8),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention(S, H, KVH, D, ps, dtype):
    k = jax.random.split(jax.random.PRNGKey(11), 2)
    B = 3
    q = jax.random.normal(k[0], (B, H, D), dtype)
    kk, vv, pool_k, pool_v, tables = _paged_pool(k[1], B, S, KVH, D, ps,
                                                 dtype=dtype)
    cl = jnp.array([S // 3, S, 1], jnp.int32)
    o_r, l_r = ref.decode_attention(q, kk, vv, cl, return_lse=True)
    o_p, l_p = pda_pallas(q, pool_k, pool_v, tables, cl, interpret=True)
    np.testing.assert_allclose(np.array(o_p, np.float32),
                               np.array(o_r, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.array(l_p), np.array(l_r), atol=1e-3, rtol=1e-3)


def test_paged_decode_matches_ref_paged_oracle():
    """The ref paged oracle (gather pages -> contiguous -> ref decode) and
    the Pallas table-gather kernel agree; garbage in unowned pool pages and
    in owned-but-dead table tails must not leak into either."""
    k = jax.random.split(jax.random.PRNGKey(12), 2)
    B, S, H, KVH, D, ps = 2, 64, 4, 2, 32, 8
    q = jax.random.normal(k[0], (B, H, D))
    _, _, pool_k, pool_v, tables = _paged_pool(k[1], B, S, KVH, D, ps)
    cl = jnp.array([13, 50], jnp.int32)  # mid-page raggedness
    o_r, l_r = ref.paged_decode_attention(q, pool_k, pool_v, tables, cl,
                                          return_lse=True)
    o_p, l_p = pda_pallas(q, pool_k, pool_v, tables, cl, interpret=True)
    np.testing.assert_allclose(np.array(o_p), np.array(o_r), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.array(l_p), np.array(l_r), atol=1e-3, rtol=1e-3)


# --------------------------------------------------------------------------- #
# fused sampling (hidden @ head -> temperature -> sample, no HBM logits)
# --------------------------------------------------------------------------- #
def _sampler_inputs(key, B, d, V, Vp=None):
    k1, k2 = jax.random.split(key)
    h = jax.random.normal(k1, (B, d), jnp.float32)
    w = jax.random.normal(k2, (d, Vp or V), jnp.float32) * 0.3
    return h, w


def test_fused_sample_greedy_bitwise():
    """inv_temp == 0 must reduce to exact argmax over the true logits,
    including jnp.argmax's first-max tie-breaking, and the returned logprob
    is the untempered log_softmax at that token."""
    h, w = _sampler_inputs(jax.random.PRNGKey(13), 4, 32, 384)
    # manufacture ties: duplicate a column block
    w = w.at[:, 100].set(w[:, 300])
    logits = h @ w
    seeds = jnp.arange(4, dtype=jnp.int32)
    tok, lp = fs_pallas(h, w, seeds, jnp.zeros(4), interpret=True)
    want = jnp.argmax(logits, axis=-1)
    assert np.array_equal(np.array(tok), np.array(want))
    want_lp = jax.nn.log_softmax(logits, axis=-1)[jnp.arange(4), want]
    np.testing.assert_allclose(np.array(lp), np.array(want_lp),
                               atol=1e-5, rtol=1e-5)


def test_fused_sample_logprob_is_untempered():
    """Sampled under temperature != 1, the logprob is still the UNTEMPERED
    distribution's log_softmax at the sampled token (the behaviour-policy
    contract of rl.rollout)."""
    h, w = _sampler_inputs(jax.random.PRNGKey(14), 8, 32, 256)
    logits = h @ w
    seeds = jnp.arange(8, dtype=jnp.int32)
    tok, lp = fs_pallas(h, w, seeds, jnp.full((8,), 1.0 / 0.7), interpret=True)
    want_lp = jax.nn.log_softmax(logits, axis=-1)[jnp.arange(8), tok]
    np.testing.assert_allclose(np.array(lp), np.array(want_lp),
                               atol=1e-5, rtol=1e-5)


def test_fused_sample_vocab_mask_never_sampled():
    """Padded vocab columns (vocab_size < padded width) must have zero
    sampling probability at any temperature."""
    V, Vp = 250, 256
    h, w = _sampler_inputs(jax.random.PRNGKey(15), 16, 32, V, Vp)
    # make the padded tail maximally attractive
    w = w.at[:, V:].set(10.0)
    for it in (0.0, 1.0, 2.0):
        for s in range(8):
            seeds = jnp.arange(16, dtype=jnp.int32) + 16 * s
            tok, _ = fs_pallas(h, w, seeds, jnp.full((16,), it),
                               vocab_size=V, interpret=True)
            assert int(jnp.max(tok)) < V


def test_fused_sample_statistics_match_softmax():
    """Empirical draw frequencies track softmax(logits/T) within 4 sigma —
    the hash-Gumbel stream is a different RNG than jax.random.categorical,
    so equivalence is distributional, not bitwise."""
    d, V, N, temp = 16, 8, 4000, 0.9
    h, w = _sampler_inputs(jax.random.PRNGKey(16), 1, d, V)
    logits = (h @ w)[0]
    p = np.array(jax.nn.softmax(logits / temp))
    h_rep = jnp.broadcast_to(h, (N, d))
    seeds = jnp.arange(N, dtype=jnp.int32)
    tok, _ = fs_pallas(h_rep, w, seeds, jnp.full((N,), 1.0 / temp),
                       interpret=True)
    counts = np.bincount(np.array(tok), minlength=V)
    for t in range(V):
        sigma = max((N * p[t] * (1 - p[t])) ** 0.5, 1.0)
        assert abs(counts[t] - N * p[t]) < 4 * sigma, (t, counts[t], N * p[t])


def test_fused_sample_block_v_invariance():
    """The online max/lse/winner accumulation must not depend on the vocab
    tiling (512-wide vs full-width single tile)."""
    h, w = _sampler_inputs(jax.random.PRNGKey(17), 4, 32, 1024)
    seeds = jnp.arange(4, dtype=jnp.int32)
    it = jnp.full((4,), 1.25)
    tok_a, lp_a = fs_pallas(h, w, seeds, it, block_v=256, interpret=True)
    tok_b, lp_b = fs_pallas(h, w, seeds, it, block_v=1024, interpret=True)
    assert np.array_equal(np.array(tok_a), np.array(tok_b))
    np.testing.assert_allclose(np.array(lp_a), np.array(lp_b),
                               atol=1e-5, rtol=1e-5)


def test_ref_fused_sample_matches_op_sequence():
    """The ref oracle is bitwise the historical decode-path op sequence
    (sample_token + untempered log_softmax gather) — the anchor the engines'
    ref dispatch mode relies on."""
    from repro.kernels import ref as kref
    from repro.rl.rollout import sample_token

    h, w = _sampler_inputs(jax.random.PRNGKey(18), 4, 32, 256)
    logits = h @ w
    key = jax.random.PRNGKey(99)
    for temp in (0.0, 0.7, 1.0):
        tok, lp = kref.fused_sample(h, w, key, temp)
        want = sample_token(logits, key, temp)
        assert np.array_equal(np.array(tok), np.array(want))
        want_lp = jax.nn.log_softmax(logits, axis=-1)[jnp.arange(4), want]
        assert np.array_equal(np.array(lp), np.array(want_lp))


def test_top_p_filter():
    from repro.kernels import ref as kref

    logits = jnp.log(jnp.array([[0.5, 0.3, 0.15, 0.05]]))
    # top_p >= 1 is the identity OBJECT (python-level skip stays bitwise)
    assert kref.top_p_filter(logits, 1.0) is logits

    def kept(top_p):  # NEG_INF is a finite sentinel (-1e30), not -inf
        return (np.array(kref.top_p_filter(logits, top_p))[0] > -1e29).tolist()

    assert kept(0.75) == [True, True, False, False]
    # the top-1 token always survives, even for tiny top_p
    assert kept(1e-9) == [True, False, False, False]
def _quantized_cache(key, B, S, KVH, D):
    from repro.models.lm import quant_kv

    k1, k2 = jax.random.split(key)
    kk = jax.random.normal(k1, (B, S, KVH, D), jnp.bfloat16)
    vv = jax.random.normal(k2, (B, S, KVH, D), jnp.bfloat16)
    kq, ks = quant_kv(kk)
    vq, vs = quant_kv(vv)
    return kq, vq, ks, vs


@pytest.mark.parametrize("S,H,KVH,D,block", [
    (128, 4, 2, 32, 64),   # GQA, per-slot varied fills
    (256, 4, 4, 32, 64),   # MHA
    (512, 8, 2, 64, 128),  # larger cache
    (256, 16, 1, 32, 64),  # MQA
])
@pytest.mark.parametrize("window", [None, 96])
def test_decode_attention_quant(S, H, KVH, D, block, window):
    """Fused-dequant Pallas kernel vs the dequantize-up-front oracle, across
    per-slot variable cache_len (the continuous engine's slot fills)."""
    k = jax.random.split(jax.random.PRNGKey(5), 2)
    B = 3
    q = jax.random.normal(k[0], (B, H, D), jnp.float32)
    kq, vq, ks, vs = _quantized_cache(k[1], B, S, KVH, D)
    cl = jnp.array([S // 3, S, 1], jnp.int32)
    o_r, l_r = ref.decode_attention_quant(
        q, kq, vq, ks, vs, cl, window=window, return_lse=True)
    o_p, l_p = daq_pallas(q, kq, vq, ks, vs, cl, window=window,
                          block_s=block, interpret=True)
    np.testing.assert_allclose(np.array(o_p, np.float32),
                               np.array(o_r, np.float32), atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.array(l_p), np.array(l_r),
                               atol=1e-2, rtol=1e-2)


def test_decode_attention_quant_matches_unfused():
    """The fused kernel must agree with dequantizing the whole cache and
    running the plain kernel — the exact computation it replaces in
    ``lm._decode_quant``."""
    from repro.models.lm import dequant_kv

    k = jax.random.split(jax.random.PRNGKey(6), 2)
    B, S, H, KVH, D = 2, 256, 4, 2, 32
    q = jax.random.normal(k[0], (B, H, D), jnp.float32)
    kq, vq, ks, vs = _quantized_cache(k[1], B, S, KVH, D)
    cl = jnp.array([77, 200], jnp.int32)
    o_fused, l_fused = daq_pallas(q, kq, vq, ks, vs, cl, block_s=64,
                                  interpret=True)
    o_unf, l_unf = da_pallas(q, dequant_kv(kq, ks), dequant_kv(vq, vs), cl,
                             block_s=64, interpret=True)
    np.testing.assert_allclose(np.array(o_fused, np.float32),
                               np.array(o_unf, np.float32),
                               atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.array(l_fused), np.array(l_unf),
                               atol=1e-2, rtol=1e-2)


# --------------------------------------------------------------------------- #
# SSD
# --------------------------------------------------------------------------- #
def _ssd_inputs(key, b, s, nh, p, g, n, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (b, s, nh, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    Bm = jax.random.normal(ks[3], (b, s, g, n), dtype)
    Cm = jax.random.normal(ks[4], (b, s, g, n), dtype)
    D = jax.random.normal(ks[5], (nh,))
    return x, dt, A, Bm, Cm, D


@pytest.mark.parametrize("s,nh,p,g,n,chunk", [
    (64, 2, 16, 1, 16, 16),
    (128, 4, 32, 2, 16, 32),
    (256, 4, 64, 4, 32, 64),
    (128, 8, 64, 1, 128, 128),  # mamba2-like (ngroups=1, N=128)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_vs_scan(s, nh, p, g, n, chunk, dtype):
    x, dt, A, Bm, Cm, D = _ssd_inputs(jax.random.PRNGKey(5), 2, s, nh, p, g, n, dtype)
    y_r, h_r = ref.ssd_scan(x, dt, A, Bm, Cm, D, return_state=True)
    y_p, h_p = ssd_pallas(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    # fp32 tol: chunked recurrence vs sequential scan accumulate in different
    # orders; the worst observed element error varies with the jax/XLA version
    # (~3e-4 on CPU jax 0.4.x), so leave headroom above it
    t = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.array(y_p, np.float32), np.array(y_r, np.float32), **t)
    np.testing.assert_allclose(np.array(h_p), np.array(h_r), atol=1e-3, rtol=1e-3)


def test_ssd_chunked_ref_matches_scan():
    x, dt, A, Bm, Cm, D = _ssd_inputs(jax.random.PRNGKey(6), 2, 96, 4, 8, 2, 8)
    y1 = ref.ssd_scan(x, dt, A, Bm, Cm, D)
    y2 = ref.ssd_chunked(x, dt, A, Bm, Cm, D, chunk=32)
    np.testing.assert_allclose(np.array(y1), np.array(y2), atol=1e-4, rtol=1e-4)


def test_ssd_decode_matches_scan_prefix():
    b, s, nh, p, g, n = 2, 16, 4, 8, 2, 8
    x, dt, A, Bm, Cm, D = _ssd_inputs(jax.random.PRNGKey(7), b, s, nh, p, g, n)
    y_scan = ref.ssd_scan(x, dt, A, Bm, Cm, D)
    h = jnp.zeros((b, nh, p, n))
    for t in range(s):
        y_t, h = ref.ssd_decode_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D, h)
        np.testing.assert_allclose(np.array(y_t), np.array(y_scan[:, t]),
                                   atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------- #
# rmsnorm
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("shape", [(4, 64), (3, 100, 64), (7, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    k = jax.random.split(jax.random.PRNGKey(8), 2)
    x = jax.random.normal(k[0], shape, dtype)
    w = (jax.random.normal(k[1], (shape[-1],)) * 0.1).astype(dtype)
    y_r = ref.rmsnorm(x, w)
    y_p = rn_pallas(x, w, block_rows=32, interpret=True)
    np.testing.assert_allclose(np.array(y_p, np.float32),
                               np.array(y_r, np.float32), **tol(dtype))


# --------------------------------------------------------------------------- #
# custom_vjp: the Pallas forward with the reference's backward
# --------------------------------------------------------------------------- #
@pytest.fixture
def interpret_mode():
    from repro.kernels import ops

    ops.set_mode("interpret")
    try:
        yield ops
    finally:
        ops.set_mode(None)


def _grads(fn, args):
    """Gradient of a loss whose cotangent depends on the forward's value, so
    the Pallas forward's output feeds the backward."""
    def loss(*a):
        out = fn(*a)
        outs = out if isinstance(out, tuple) else (out,)
        return sum(jnp.sum(jnp.sin(o.astype(jnp.float32))) for o in outs)

    return jax.grad(loss, argnums=tuple(range(len(args))))(*args)


def _assert_grads_close(got, want):
    # f32 inputs: the two forwards differ only by accumulation order (~1e-6
    # relative), and the shared reference backward carries that through
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.array(g), np.array(w),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("H,KVH,window", [
    (4, 4, None),   # causal MHA
    (4, 4, 48),     # sliding window
    (8, 2, None),   # GQA group 4
])
def test_flash_attention_grad_matches_ref(interpret_mode, H, KVH, window):
    k = jax.random.split(jax.random.PRNGKey(20), 3)
    B, S, D = 2, 128, 32
    q = jax.random.normal(k[0], (B, S, H, D))
    kk = jax.random.normal(k[1], (B, S, KVH, D))
    vv = jax.random.normal(k[2], (B, S, KVH, D))
    got = _grads(lambda *a: interpret_mode.flash_attention(
        *a, causal=True, window=window), (q, kk, vv))
    want = _grads(lambda *a: ref.flash_attention(
        *a, causal=True, window=window), (q, kk, vv))
    _assert_grads_close(got, want)


def test_rmsnorm_grad_matches_ref(interpret_mode):
    k = jax.random.split(jax.random.PRNGKey(21), 2)
    x = jax.random.normal(k[0], (3, 40, 64))
    w = jax.random.normal(k[1], (64,)) * 0.1
    got = _grads(interpret_mode.rmsnorm, (x, w))
    want = _grads(ref.rmsnorm, (x, w))
    _assert_grads_close(got, want)


def test_ssd_grad_matches_ref(interpret_mode):
    args = _ssd_inputs(jax.random.PRNGKey(22), 2, 64, 4, 16, 2, 16)
    got = _grads(lambda *a: interpret_mode.ssd(
        *a, chunk=16, return_state=True), args)
    want = _grads(lambda *a: ref.ssd_chunked(
        *a, chunk=16, return_state=True), args)
    _assert_grads_close(got, want)
