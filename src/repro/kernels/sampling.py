"""Fused decode-step sampler: LM-head matmul + temperature + Gumbel-max
sampling + logprob, one Pallas kernel, vocab tile by vocab tile.

The pre-fusion decode step materializes the full (B, padded_vocab) logits in
HBM, then ``jax.random.categorical`` reads them back (twice, counting the
logprob gather) — at small batch the decode step is *head-bandwidth* bound,
not attention bound. This kernel streams the head weight tiles once, keeps
the per-row online state (running max / sum-exp for the logprob, running
Gumbel-max winner for the sample) in VMEM scalars, and emits only (token,
logprob) per row: the logits never exist as an array.

Sampling uses the Gumbel-max trick: ``argmax(z * inv_temp + g)`` with
``g = -log(-log(u))`` draws exactly from ``softmax(z / temp)``, and an argmax
folds into the online tile sweep where a CDF inversion would not. Uniforms
come from a counter-based integer hash (splitmix32 over seed x vocab index):
stateless, identical in interpret mode and on TPU, and independent per
(row, token) — statistically equivalent to ``jax.random.categorical``'s
stream but not bitwise-identical to it (that contract lives in
``kernels/ops.py``: the ref dispatch path IS the old op sequence).

``inv_temp`` is per row with 0.0 meaning greedy (argmax of the raw logits) —
one kernel serves both the rollout engine (one shared temperature) and the
serving engine (per-request temperatures). Per-row seeds (int32) and inverse
temperatures (f32) arrive as (B, 1) column tiles beside the hidden rows.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import _pick_block_s

NEG_INF = -1e30
LANES = 128


def _hash_u32(x: jax.Array) -> jax.Array:
    """splitmix32-style avalanche hash on uint32 (wrapping arithmetic)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _uniform_01(seed: jax.Array, pos: jax.Array) -> jax.Array:
    """Counter-based uniform in the OPEN interval (0, 1): hash (seed, pos),
    keep 24 bits, center on the half-ulp grid so log(u) and log(-log(u))
    are always finite."""
    mixed = pos.astype(jnp.uint32) * jnp.uint32(0x9E3779B1) + seed
    bits = _hash_u32(mixed) >> jnp.uint32(8)
    # 24-bit values are exact in int32, and signed int->float is what
    # every backend converts natively
    bits = jax.lax.bitcast_convert_type(bits, jnp.int32)
    return (bits.astype(jnp.float32) + 0.5) * jnp.float32(1.0 / (1 << 24))


def _sample_kernel(
    seed_ref,  # (block_b, 1) int32 per-row hash seeds
    it_ref,  # (block_b, 1) f32 inverse temperature (0 = greedy)
    h_ref,
    w_ref,
    tok_ref,
    lp_ref,
    m_ref,
    l_ref,
    by_ref,
    bz_ref,
    bi_ref,
    *,
    block_v: int,
    num_v_blocks: int,
    vocab_size: int,
):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        by_ref[...] = jnp.full_like(by_ref, NEG_INF)
        bz_ref[...] = jnp.full_like(bz_ref, NEG_INF)
        bi_ref[...] = jnp.zeros_like(bi_ref)

    z = jax.lax.dot_general(
        h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (block_b, block_v) untempered logits tile
    pos = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    pv = pos < vocab_size
    z = jnp.where(pv, z, NEG_INF)

    # online log-sum-exp of the untempered logits (for the logprob)
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(z, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.where(pv, jnp.exp(z - m_new), 0.0)
    l_new = jnp.exp(m_prev - m_new) * l_prev + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    # Gumbel-max score (greedy rows score the raw logits)
    inv_temp = it_ref[...]
    seed = jax.lax.bitcast_convert_type(seed_ref[...], jnp.uint32)
    u = _uniform_01(seed, pos)
    g = -jnp.log(-jnp.log(u))
    y = jnp.where(inv_temp == 0.0, z, z * inv_temp + g)
    y = jnp.where(pv, y, NEG_INF)

    # running winner: strictly-better keeps the earliest tile on ties, and
    # the min-index trick inside a tile matches argmax's first-max rule
    t_max = jnp.max(y, axis=1, keepdims=True)
    t_arg = jnp.min(jnp.where(y == t_max, pos, jnp.int32(2**30)),
                    axis=1, keepdims=True)
    z_at = jnp.max(jnp.where(pos == t_arg, z, NEG_INF), axis=1, keepdims=True)
    better = t_max > by_ref[:, :1]
    by_ref[...] = jnp.broadcast_to(
        jnp.where(better, t_max, by_ref[:, :1]), by_ref.shape)
    bz_ref[...] = jnp.broadcast_to(
        jnp.where(better, z_at, bz_ref[:, :1]), bz_ref.shape)
    bi_ref[...] = jnp.broadcast_to(
        jnp.where(better, t_arg, bi_ref[:, :1]), bi_ref.shape)

    @pl.when(vi == num_v_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-20)
        lse = m_ref[:, :1] + jnp.log(l)
        tok_ref[...] = bi_ref[...]
        lp_ref[...] = jnp.broadcast_to(bz_ref[:, :1] - lse, lp_ref.shape)


def _pick_block_b(B: int, want: int = 256) -> int:
    """Rows per grid step: the whole batch when it fits, else the largest
    multiple-of-8 divisor of B that does (a legal sublane tile either way)."""
    return B if B <= want else _pick_block_s(B, want, 8)


def fused_sample(
    h: jax.Array,  # (B, d)
    w_head: jax.Array,  # (d, Vp)
    seeds: jax.Array,  # (B,) int32 per-row hash seeds
    inv_temp: jax.Array,  # (B,) f32; 0.0 = greedy, else 1/temperature
    *,
    vocab_size: Optional[int] = None,
    block_v: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused head+sampler. Returns (token (B,) int32, logprob (B,) f32 of the
    sampled token under the *untempered* masked distribution — the
    behaviour-logprob contract of ``rl/rollout.generate``).

    Grid (B / block_b, Vp / block_v) with the vocab axis minor: each step
    multiplies a (block_b, d) row block by one (d, block_v) head tile, so
    the head weights stream once per row block — once per decode step when
    the whole batch is one block. ``block_v`` is a multiple of 128 whenever
    the padded vocab is (a whole-vocab tile otherwise)."""
    B, d = h.shape
    Vp = w_head.shape[1]
    vocab = Vp if vocab_size is None else vocab_size
    block_v = _pick_block_s(Vp, block_v, LANES)
    nv = Vp // block_v
    block_b = _pick_block_b(B)

    kernel = functools.partial(
        _sample_kernel, block_v=block_v, num_v_blocks=nv, vocab_size=vocab)
    row = lambda b, vi: (b, 0)
    tok, lp = pl.pallas_call(
        kernel,
        grid=(B // block_b, nv),
        in_specs=[
            pl.BlockSpec((block_b, 1), row),
            pl.BlockSpec((block_b, 1), row),
            pl.BlockSpec((block_b, d), row),
            pl.BlockSpec((d, block_v), lambda b, vi: (0, vi)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, LANES), row),
            pl.BlockSpec((block_b, LANES), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, LANES), jnp.int32),
            jax.ShapeDtypeStruct((B, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, LANES), jnp.float32),  # m
            pltpu.VMEM((block_b, LANES), jnp.float32),  # l
            pltpu.VMEM((block_b, LANES), jnp.float32),  # best gumbel score
            pltpu.VMEM((block_b, LANES), jnp.float32),  # best untempered logit
            pltpu.VMEM((block_b, LANES), jnp.int32),  # best index
        ],
        interpret=interpret,
    )(seeds.astype(jnp.int32)[:, None], inv_temp.astype(jnp.float32)[:, None],
      h, w_head)
    return tok[:, 0], lp[:, 0]
