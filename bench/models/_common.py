"""What every kind of model shares: float32 (or float8-operand) arithmetic
at the highest matmul precision, the norm and rotary positions, the
initialisers, the parameters around the layer stacks, and the tree and
forward of a kind whose layers repeat one block (a pattern of 1)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _normal(key, shape, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
        jnp.bfloat16)


def _dense(key, shape):
    return _normal(key, shape, 1.0 / shape[0] ** 0.5)


def _lm_params(a, stacks, embed_key, head_key):
    """The tree around the layer stacks: the embedding, the final norm and,
    where it is not tied, the head, as the program's ``model.init`` lays
    them out."""
    v, d = a["vocab_padded"], a["d"]
    p = {"embed": _normal(embed_key, (v, d), 0.02), "blocks": stacks,
         "final_norm": {"w": jnp.zeros((d,), jnp.float32)}}
    if not a["tied"]:
        p["lm_head"] = _normal(head_key, (d, v), 1.0 / d ** 0.5)
    return p


def _init_one_stack(a, key, init_block):
    """The whole tree of a pattern of 1: one stack of ``init_block(a, key)``
    over the layers, ``blocks[0]``, inside :func:`_lm_params`."""
    ks = jax.random.split(key, 3)
    blocks = jax.vmap(lambda k: init_block(a, k))(
        jax.random.split(ks[0], a["layers"]))
    return _lm_params(a, [blocks], ks[1], ks[2])


def _hidden_one_stack(a, params, tokens, layer):
    """Embedding, ``layer(h, p)`` scanned and rematerialised over
    ``blocks[0]``, final norm: float32 throughout."""
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    step = jax.checkpoint(lambda h, p: (layer(h, p), None))
    h, _ = jax.lax.scan(step, h, params["blocks"][0])
    return _rmsnorm(h, params["final_norm"]["w"], a["eps"])


def _q(x, precision):
    """An operand as the matmul reads it: float32, or rounded to float8 with
    one scale per tensor (the gradient passes through the rounding)."""
    x = x.astype(jnp.float32)
    if precision == "f32":
        return x
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX)
    rounded = (x / scale).astype(F8).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(x, w, precision):
    return jnp.matmul(_q(x, precision), _q(w, precision), precision=HIGHEST)


def _einsum(spec, x, y, precision):
    return jnp.einsum(spec, _q(x, precision), _q(y, precision),
                      precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    """x (b, S, H, D), positions 0..S-1, halves rotated."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
