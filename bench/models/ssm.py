"""Mamba2 blocks: a pre-norm SSD mixer (state-space duality), computed in
its chunked dual form, with no MLP.

One repeating layer kind, a pattern of 1: the parameters hold one stack of
blocks, ``blocks[0]``, stacked over the layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.models._common import (HIGHEST, _hidden_one_stack,
                                  _init_one_stack, _mm, _normal, _rmsnorm)

SSD_CHUNK = 128


def arch(lay: dict) -> dict:
    a = dict(d_inner=lay["ssm_expand"] * lay["d_model"],
             state=lay["ssm_state"], ssm_head_dim=lay["ssm_headdim"],
             groups=lay["ssm_ngroups"], conv=lay["ssm_conv"])
    a["ssm_heads"] = a["d_inner"] // a["ssm_head_dim"]
    return a


# --------------------------------------------------------------------------- #
# initialisation (the layout's stated scheme, from the seed)
# --------------------------------------------------------------------------- #
def _init_ssm(a, key):
    d, din, n, nh = a["d"], a["d_inner"], a["state"], a["ssm_heads"]
    g, kw = a["groups"], a["conv"]
    ks = jax.random.split(key, 8)
    s = 1.0 / d ** 0.5
    dt = jnp.exp(jax.random.uniform(ks[6], (nh,), jnp.float32)
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    return {"w_z": _normal(ks[0], (d, din), s),
            "w_x": _normal(ks[1], (d, din), s),
            "w_B": _normal(ks[2], (d, g * n), s),
            "w_C": _normal(ks[3], (d, g * n), s),
            "w_dt": _normal(ks[4], (d, nh), s),
            "conv_x": _normal(ks[5], (kw, din), 1.0 / kw),
            "conv_bc": _normal(ks[7], (kw, 2 * g * n), 1.0 / kw),
            "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
            "D": jnp.ones((nh,), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm_w": jnp.zeros((din,), jnp.float32),
            "w_out": _normal(jax.random.fold_in(key, 99), (din, d),
                             1.0 / din ** 0.5)}


def _init_block(a, key):
    ks = jax.random.split(key, 4)
    return {"norm1": {"w": jnp.zeros((a["d"],), jnp.float32)},
            "ssm": _init_ssm(a, ks[0])}


def init(a, key):
    return _init_one_stack(a, key, _init_block)


# --------------------------------------------------------------------------- #
# the forward
# --------------------------------------------------------------------------- #
def _causal_conv(x, w):
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, i:i + S] * w[i].astype(jnp.float32) for i in range(K))


def _ssd(x, dt, A, Bm, Cm):
    """Chunked dual form of h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T,
    y_t = C_t . h_t. x (b,S,H,P); dt (b,S,H); B, C (b,S,H,N)."""
    b, S, H, P = x.shape
    T = min(SSD_CHUNK, S)
    nc = S // T
    ch = lambda t: t.reshape(b, nc, T, *t.shape[2:])
    xc, dtc, Bc, Cc = ch(x), ch(dt), ch(Bm), ch(Cm)
    cs = jnp.cumsum(dtc * A, axis=2)  # (b, nc, T, H) log-decay from chunk start
    diff = cs[:, :, :, None, :] - cs[:, :, None, :, :]  # (b,nc,i,j,H)
    lower = jnp.tril(jnp.ones((T, T), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    cb = jnp.einsum("bcihn,bcjhn->bcijh", Cc, Bc, precision=HIGHEST)
    y = jnp.einsum("bcijh,bcjhp->bcihp", cb * decay, dtc[..., None] * xc,
                   precision=HIGHEST)
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)  # (b, nc, T, H)
    states = jnp.einsum("bcjhn,bcjhp->bchpn", Bc * (dtc * to_end)[..., None],
                        xc, precision=HIGHEST)
    chunk_decay = jnp.exp(cs[:, :, -1, :])  # (b, nc, H)

    def carry(hprev, inp):
        st, dec = inp
        return dec[..., None, None] * hprev + st, hprev

    _, h_in = jax.lax.scan(carry, jnp.zeros((b, H, P, Bm.shape[-1])),
                           (jnp.moveaxis(states, 1, 0),
                            jnp.moveaxis(chunk_decay, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)  # state entering each chunk
    y = y + jnp.einsum("bcihn,bchpn->bcihp", Cc * jnp.exp(cs)[..., None],
                       h_in, precision=HIGHEST)
    return y.reshape(b, S, H, P)


def _ssm(a, p, h, pr):
    b, S, _ = h.shape
    din, n, nh, g = a["d_inner"], a["state"], a["ssm_heads"], a["groups"]
    z = _mm(h, p["w_z"], pr)
    x = _causal_conv(_mm(h, p["w_x"], pr), p["conv_x"])
    bc = jnp.concatenate([_mm(h, p["w_B"], pr), _mm(h, p["w_C"], pr)], -1)
    bc = jax.nn.silu(_causal_conv(bc, p["conv_bc"]))
    x = jax.nn.silu(x)
    dt = jax.nn.softplus(_mm(h, p["w_dt"], pr) + p["dt_bias"])
    rep = lambda t: jnp.repeat(t.reshape(b, S, g, n), nh // g, axis=2)
    Bm, Cm = rep(bc[..., :g * n]), rep(bc[..., g * n:])
    xh = x.reshape(b, S, nh, a["ssm_head_dim"])
    y = _ssd(xh, dt, -jnp.exp(p["A_log"]), Bm, Cm) + p["D"][:, None] * xh
    y = _rmsnorm(y.reshape(b, S, din) * jax.nn.silu(z), p["norm_w"], a["eps"])
    return _mm(y, p["w_out"], pr)


def hidden(a, params, tokens, pr):
    def layer(h, p):
        return h + _ssm(a, p["ssm"], _rmsnorm(h, p["norm1"]["w"], a["eps"]),
                        pr)

    return _hidden_one_stack(a, params, tokens, layer)


# --------------------------------------------------------------------------- #
# FLOPs
# --------------------------------------------------------------------------- #
def matmul_params(lay: dict) -> int:
    d, v, layers = lay["d_model"], lay["vocab_size"], lay["num_layers"]
    din = lay["ssm_expand"] * d
    gn = lay["ssm_ngroups"] * lay["ssm_state"]
    heads = din // lay["ssm_headdim"]
    per_layer = d * (2 * din + 2 * gn + heads) + din * d
    return layers * per_layer + d * v


def forward_flops(lay: dict, lens) -> float:
    """2N a token, plus in each layer the SSD state's update and read, 4 *
    d_inner * d_state, after a depthwise conv of 2 * conv * (d_inner + 2 *
    groups * d_state). Nothing grows with the context."""
    din = lay["ssm_expand"] * lay["d_model"]
    n, g, k = lay["ssm_state"], lay["ssm_ngroups"], lay["ssm_conv"]
    mixing = 4 * din * n + 2 * k * (din + 2 * g * n)
    tokens = float(np.asarray(lens, np.float64).sum())
    return tokens * (2 * matmul_params(lay) + lay["num_layers"] * mixing)
