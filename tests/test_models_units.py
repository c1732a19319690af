"""Model-layer unit tests: MoE dispatch equivalence, RoPE properties,
causal conv, norms, tokenizer, pattern compression."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.configs.base import ModelConfig
from repro.data.tokenizer import ByteTokenizer
from repro.models import lm, moe, ssm
from repro.models.layers import apply_norm, init_norm, rope


def moe_cfg(e=4, k=2):
    return reduced(ARCHS["mixtral-8x7b"], num_experts=e, num_experts_per_tok=k,
                   d_model=32, d_ff=16, vocab_size=256)


def test_moe_dense_equals_sparse_dispatch():
    """The GSPMD-friendly dense dispatch and the gather-based top-k dispatch
    must produce identical outputs."""
    cfg = moe_cfg()
    p = moe.init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model), jnp.float32)
    y1, a1 = moe.apply_moe(cfg, p, x)
    y2, a2 = moe.apply_moe_topk_sparse(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(float(a1), float(a2), atol=1e-5)


def test_moe_aux_loss_balanced_router():
    """Uniform router -> aux loss ~= num_experts * E * (1/E)*(1/E) * ... = 1."""
    cfg = moe_cfg(e=4, k=1)
    p = moe.init_moe(cfg, jax.random.PRNGKey(0))
    p = dict(p, router=jnp.zeros_like(p["router"]))  # uniform logits
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model))
    _, aux = moe.apply_moe(cfg, p, x)
    # density ~uniform over ties -> aux ~ E * sum(1/E * 1/E) = 1
    assert 0.8 < float(aux) < 1.3


def test_rope_preserves_norm_and_relative_phase():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    pos = jnp.arange(8)[None, :]
    y = rope(x, pos, 10_000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-5)
    # dot products depend only on relative distance
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 16))
    def score(pq, pk):
        qr = rope(q, jnp.array([[pq]]), 1e4)
        kr = rope(k, jnp.array([[pk]]), 1e4)
        return float(jnp.sum(qr * kr))
    assert score(3, 1) == pytest.approx(score(10, 8), abs=1e-4)


def test_causal_conv_matches_explicit():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 4))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 4))
    y, state = ssm._causal_conv(x, w)
    xp = np.concatenate([np.zeros((2, 2, 4)), np.asarray(x)], axis=1)
    want = sum(xp[:, i:i + 10] * np.asarray(w)[i] for i in range(3))
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(x[:, -2:]), atol=1e-6)


def test_causal_conv_streaming_equals_batch():
    """Stepwise conv with carried state == full-sequence conv."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 4))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 4))
    y_full, _ = ssm._causal_conv(x, w)
    state = None
    outs = []
    for t in range(6):
        y_t, state = ssm._causal_conv(x[:, t:t + 1], w, state)
        outs.append(y_t)
    y_step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full), atol=1e-5)


def test_norms():
    cfg_rms = reduced(ARCHS["deepseek-67b"], d_model=16)
    cfg_ln = dataclasses.replace(cfg_rms, norm_type="layernorm")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 16)) * 5 + 2
    y_rms = apply_norm(cfg_rms, init_norm(cfg_rms), x)
    y_ln = apply_norm(cfg_ln, init_norm(cfg_ln), x)
    # layernorm removes the mean, rmsnorm does not
    assert abs(float(jnp.mean(y_ln))) < 1e-4
    assert abs(float(jnp.mean(y_rms))) > 1e-2
    np.testing.assert_allclose(
        np.mean(np.square(np.asarray(y_rms, np.float32)), -1), 1.0, rtol=0.05)


def test_pattern_compression():
    assert lm.pattern_length(ARCHS["deepseek-67b"]) == 1
    assert lm.pattern_length(ARCHS["mixtral-8x7b"]) == 1
    assert lm.pattern_length(ARCHS["jamba-v0.1-52b"]) == 8
    assert lm.pattern_length(ARCHS["mamba2-2.7b"]) == 1
    kinds = ARCHS["jamba-v0.1-52b"].layer_kinds()
    assert kinds[4][0] == "attn" and kinds[0][0] == "ssm"
    assert sum(1 for k in kinds if k[0] == "attn") == 4  # 1:7 interleave
    assert sum(1 for k in kinds if k[1] == "moe") == 16


def test_tokenizer_roundtrip():
    tok = ByteTokenizer()
    for text in ("12+34=", "hello world", "ünïcødé"):
        ids = tok.encode(text, eos=True)
        assert tok.decode(ids) == text
    assert tok.decode(tok.encode("abc")) == "abc"
    assert tok.vocab_size == 259


def test_vocab_padding_exact():
    for arch, cfg in ARCHS.items():
        assert cfg.padded_vocab % 256 == 0
        assert cfg.padded_vocab >= cfg.vocab_size
        assert cfg.padded_vocab - cfg.vocab_size < 256


def test_ring_cache_width():
    cfg = ARCHS["mixtral-8x7b"]
    caches = jax.eval_shape(
        lambda: lm.init_caches(cfg, batch=1, smax=524_288))
    k = caches[0]["k"]
    assert k.shape[2] == 4096  # bounded at the SWA window, not 524288
    cfg2 = ARCHS["deepseek-67b"]
    caches2 = jax.eval_shape(lambda: lm.init_caches(cfg2, batch=1, smax=8192))
    assert caches2[0]["k"].shape[2] == 8192  # full attention keeps smax


def _dot_scopes(fn, *args):
    """The JAX name stack of every dot in ``fn``'s compiled program."""
    import re

    text = jax.jit(fn).lower(*args).compile().as_text()
    return [m.group(1) for m in re.finditer(
        r' dot\(.*?op_name="([^"]*)"', text)]


@pytest.mark.parametrize("arch", ["qwen2.5-7b", "mamba2-2.7b"])
def test_layer_scopes_name_every_matmul(arch):
    """Each matmul of decode (sampler fused), prefill and the loss's
    forward and backward lies under the block's ``mixer`` or ``mlp``
    named scope or the ``head`` scope; the backward's through its
    transpose name stack. Device traces name the ops by these."""
    import re

    cfg = reduced(ARCHS[arch], vocab_size=260, num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                  ssm_state=16, ssm_headdim=16)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.ones((2, 8), jnp.int32)
    caches = lm.init_caches(cfg, 2, 16)
    cache_len = jnp.full((2,), 8, jnp.int32)
    labels = jnp.ones((2, 8), jnp.int32)
    layer = re.compile(r"(^|[/(])(mixer|mlp|head)([/)]|$)")
    decode = _dot_scopes(
        lambda p, t, c, n, k: lm.decode_step_sample(cfg, p, t, c, n, k, 1.0),
        params, tokens[:, 0], caches, cache_len, jax.random.PRNGKey(1))
    prefill = _dot_scopes(lambda p, t: lm.prefill(cfg, p, t, smax=16)[0],
                          params, tokens)
    grad = _dot_scopes(jax.grad(lambda p: lm.loss_fn(
        cfg, p, {"tokens": tokens, "labels": labels})[0]), params)
    for scopes in (decode, prefill, grad):
        assert scopes and all(layer.search(s) for s in scopes), scopes
    for name in ("mixer", "head") + (("mlp",) if arch == "qwen2.5-7b"
                                     else ()):
        named = re.compile(rf"(^|[/(]){name}([/)]|$)")
        assert any(named.search(s) for s in decode), name
        assert any(named.search(s) and "transpose(" in s for s in grad), name


# --------------------------------------------------------------------------- #
# decode: the KV arena carried through the layer loop, written in place
# --------------------------------------------------------------------------- #
def _decode_cfg(window=None):
    return reduced(ARCHS["qwen2.5-7b"], vocab_size=260, num_layers=3,
                   sliding_window=window)


def _masked_write_step(cfg, params, token, caches, cache_len, unroll=False):
    """One decode step as the per-layer masked write computes it: the layer
    scan slices each layer's cache out of the arena, writes the new position
    through an elementwise select over the whole (B, W) cache, attends over
    that layer's cache and restacks the layers."""
    from repro.kernels import ref
    from repro.models import layers

    B, hd = token.shape[0], cfg.head_dim
    W = caches[0]["k"].shape[2]
    ring = cfg.sliding_window is not None and W <= cfg.sliding_window
    slot = cache_len % W if ring else cache_len
    sel = (jnp.arange(W)[None, :] == slot[:, None])[..., None]
    if ring:
        lens, window = jnp.minimum(cache_len + 1, W), None
    else:
        lens, window = cache_len + 1, cfg.sliding_window

    def block(h, xs):
        p, c = xs
        hn = layers.apply_norm(cfg, p["norm1"], h)
        q, k, v = layers.qkv_proj(cfg, p["attn"], hn, cache_len[:, None])
        kc = jnp.where(sel, k[:, 0].reshape(B, 1, -1), c["k"])
        vc = jnp.where(sel, v[:, 0].reshape(B, 1, -1), c["v"])
        o = ref.decode_attention(q[:, 0], kc.reshape(B, W, -1, hd),
                                 vc.reshape(B, W, -1, hd), lens,
                                 window=window)
        h = h + layers.out_proj(cfg, p["attn"], o)[:, None]
        h = h + layers.apply_mlp(cfg, p["mlp"],
                                 layers.apply_norm(cfg, p["norm2"], h))
        return h, {"k": kc, "v": vc}

    h = lm.embed_tokens(cfg, params, token[:, None])
    xs = (params["blocks"][0], caches[0])
    if unroll:
        ys = []
        for n in range(cfg.num_layers):
            h, y = block(h, jax.tree.map(lambda a: a[n], xs))
            ys.append(y)
        new = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        h, new = jax.lax.scan(block, h, xs)
    h = layers.apply_norm(cfg, params["final_norm"], h)
    return lm._head_logits(cfg, params, h[:, 0]), [new]


@pytest.mark.parametrize("window", [None, 8])
@pytest.mark.parametrize("unroll", [False, True])
def test_carried_arena_decode_equals_masked_write_step(window, unroll):
    """The carried-arena decode step (one row per sequence and layer written
    in place, the kernel dispatch reading layer n of the stacked arena) is
    bitwise the per-layer masked-write step, over ragged cache lengths and,
    with a window of 8 under an arena of 20, an SWA ring that wraps."""
    cfg = _decode_cfg(window)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 6), 3, 200)
    _, caches, cache_len = lm.prefill(cfg, params, prompt, smax=20)
    cache_len = cache_len - jnp.asarray([0, 2, 5], jnp.int32)  # ragged
    token = jnp.asarray([7, 9, 11], jnp.int32)
    step = jax.jit(functools.partial(lm.decode_step, cfg, unroll=unroll))
    masked = jax.jit(functools.partial(_masked_write_step, cfg,
                                       unroll=unroll))
    want_caches = caches
    for _ in range(12):  # past the ring's width when windowed
        logits, caches, next_len = step(params, token, caches, cache_len)
        want, want_caches = masked(params, token, want_caches, cache_len)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
        for got_c, want_c in zip(caches, want_caches):
            for name in ("k", "v"):
                np.testing.assert_array_equal(np.asarray(got_c[name]),
                                              np.asarray(want_c[name]))
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        cache_len = next_len


def _arena_selects(jaxpr, arena_shapes):
    """``select_n`` equations anywhere in ``jaxpr`` (sub-jaxprs included)
    whose output has one of ``arena_shapes``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "select_n" and any(
                tuple(v.aval.shape) in arena_shapes for v in eqn.outvars):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _arena_selects(sub, arena_shapes)
    return found


@pytest.mark.parametrize("window", [None, 8])
def test_decode_writes_the_arena_without_a_select(window):
    """No select with the arena's shape, or one layer's, is left in the
    decode step: a masked write over the whole cache would read and write
    the arena once per layer and step."""
    cfg = _decode_cfg(window)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    caches = lm.init_caches(cfg, 4, 20)
    arena = caches[0]["k"].shape
    jaxpr = jax.make_jaxpr(
        lambda p, t, c, n, k: lm.decode_step_sample(cfg, p, t, c, n, k, 1.0)
    )(params, jnp.ones((4,), jnp.int32), caches,
      jnp.full((4,), 6, jnp.int32), jax.random.PRNGKey(1))
    assert not _arena_selects(jaxpr.jaxpr, {arena, arena[1:]})
    # the check sees a masked write where there is one
    masked = jax.make_jaxpr(lambda t, c, n: _masked_write_step(
        cfg, params, t, c, n))(jnp.ones((4,), jnp.int32), caches,
                               jnp.full((4,), 6, jnp.int32))
    assert _arena_selects(masked.jaxpr, {arena, arena[1:]})
