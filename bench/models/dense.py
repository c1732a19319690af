"""Dense decoder blocks: pre-norm grouped-query attention with rotary
positions over every earlier token, then a SwiGLU MLP.

One repeating layer kind, a pattern of 1: the parameters hold one stack of
blocks, ``blocks[0]``, stacked over the layers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.models._common import (_dense, _einsum, _hidden_one_stack,
                                  _init_one_stack, _mm, _rmsnorm, _rope)


def arch(lay: dict) -> dict:
    return dict(heads=lay["num_heads"], heads_padded=lay["padded_heads"],
                kv_heads=lay["num_kv_heads"], head_dim=lay["head_dim"],
                d_ff=lay["d_ff"], rope_theta=lay["rope_theta"])


# --------------------------------------------------------------------------- #
# initialisation (the layout's stated scheme, from the seed)
# --------------------------------------------------------------------------- #
def _init_attention(a, key):
    d, hd, hp, kvh = a["d"], a["head_dim"], a["heads_padded"], a["kv_heads"]
    ks = jax.random.split(key, 4)
    real = (jnp.arange(hp * hd) < a["heads"] * hd).astype(jnp.bfloat16)
    return {"w_q": _dense(ks[0], (d, hp * hd)) * real[None, :],
            "w_k": _dense(ks[1], (d, kvh * hd)),
            "w_v": _dense(ks[2], (d, kvh * hd)),
            "w_o": _dense(ks[3], (hp * hd, d)) * real[:, None]}


def _init_mlp(a, key):
    ks = jax.random.split(key, 3)
    d, f = a["d"], a["d_ff"]
    return {"w_in": _dense(ks[0], (d, f)), "w_out": _dense(ks[1], (f, d)),
            "w_gate": _dense(ks[2], (d, f))}


def _init_block(a, key):
    ks = jax.random.split(key, 4)
    zeros = {"w": jnp.zeros((a["d"],), jnp.float32)}
    return {"norm1": zeros, "attn": _init_attention(a, ks[0]),
            "norm2": dict(zeros), "mlp": _init_mlp(a, ks[1])}


def init(a, key):
    return _init_one_stack(a, key, _init_block)


# --------------------------------------------------------------------------- #
# the forward
# --------------------------------------------------------------------------- #
def _attention(a, p, h, pr):
    b, S, _ = h.shape
    hp, kvh, hd = a["heads_padded"], a["kv_heads"], a["head_dim"]
    q = _rope(_mm(h, p["w_q"], pr).reshape(b, S, hp, hd), a["rope_theta"])
    k = _rope(_mm(h, p["w_k"], pr).reshape(b, S, kvh, hd), a["rope_theta"])
    v = _mm(h, p["w_v"], pr).reshape(b, S, kvh, hd)
    q = q.reshape(b, S, kvh, hp // kvh, hd)  # query head i reads kv head i // g
    s = _einsum("bqhgd,bkhd->bhgqk", q, k, pr) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = _einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, -1), v, pr)
    return _mm(o.reshape(b, S, hp * hd), p["w_o"], pr)


def _mlp(p, h, pr):
    return _mm(jax.nn.silu(_mm(h, p["w_gate"], pr)) * _mm(h, p["w_in"], pr),
               p["w_out"], pr)


def hidden(a, params, tokens, pr):
    def layer(h, p):
        h = h + _attention(a, p["attn"], _rmsnorm(h, p["norm1"]["w"],
                                                 a["eps"]), pr)
        return h + _mlp(p["mlp"], _rmsnorm(h, p["norm2"]["w"], a["eps"]),
                        pr)

    return _hidden_one_stack(a, params, tokens, layer)


# --------------------------------------------------------------------------- #
# FLOPs
# --------------------------------------------------------------------------- #
def matmul_params(lay: dict) -> int:
    d, v, layers = lay["d_model"], lay["vocab_size"], lay["num_layers"]
    h, kvh, hd = lay["num_heads"], lay["num_kv_heads"], lay["head_dim"]
    attn = d * h * hd * 2 + d * kvh * hd * 2
    per_layer = attn + 3 * d * lay["d_ff"]
    return layers * per_layer + d * v


def forward_flops(lay: dict, lens) -> float:
    """2N a token, plus causal attention: every token reads every earlier
    non-pad token and itself, 4 * heads * head_dim in each layer (scores
    and values; real heads only)."""
    n = np.asarray(lens, np.float64)
    contexts = n * (n + 1) / 2
    return float(n.sum()) * (2 * matmul_params(lay)) + float(
        contexts.sum()) * lay["num_layers"] * (
            4 * lay["num_heads"] * lay["head_dim"])
