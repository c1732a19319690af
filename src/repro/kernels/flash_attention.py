"""Flash attention Pallas TPU kernel (training / prefill path).

Causal + optional sliding-window + GQA. Grid (B, H, nQ, nK) with the K axis
minor: TPU executes the grid sequentially over the last dimension, so the
online-softmax running state (acc, m, l) lives in VMEM scratch and is carried
across K blocks. Block sizes default to 128 (MXU-aligned); q/k/v tiles are
streamed HBM->VMEM by BlockSpecs.

Layouts: q (B, Sq, H, D); k, v (B, Sk, KVH, D); out (B, Sq, H, D). The
wrapper views them lane-folded, (B, S, H*D) — a free reshape — so every tile
is a (block, D) slab picked by the head index along the last axis. Mosaic
needs a tile's last two dims divisible by (8, 128) or equal to the array's,
so compiled runs need ``D % 128 == 0``; interpret mode takes any D. Sequence
lengths that are not a block multiple are zero-padded up to one, and padded
keys are masked out.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # TPU vector lane width; m/l scratch is (block_q, LANES)


def check_lane_width(d: int, interpret: bool) -> None:
    """Compiled kernels tile the lane-folded (…, heads*d) view in d-wide
    slabs; Mosaic only accepts those when d is a multiple of 128."""
    if not interpret and d % LANES:
        raise ValueError(
            f"compiled Pallas attention needs head_dim % {LANES} == 0 (got "
            f"{d}); run such models with REPRO_KERNEL_MODE=ref")


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_k: int,
    num_k_blocks: int,
    q_offset: int,
    kv_len: Optional[int],
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- block relevance (skip fully-masked K blocks) ----
    q_lo = qi * block_q + q_offset
    q_hi = q_lo + block_q - 1
    k_lo = ki * block_k
    k_hi = k_lo + block_k - 1
    needed = jnp.bool_(True)
    if causal:
        needed &= q_hi >= k_lo
    if window is not None:
        needed &= k_hi > q_lo - window

    @pl.when(needed)
    def _compute():
        q = q_ref[...]  # (block_q, D)
        k = k_ref[...]  # (block_k, D)
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        if kv_len is not None:
            mask &= kpos < kv_len
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-20)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    assert H % KVH == 0
    check_lane_width(D, interpret)
    group = H // KVH
    scale = scale if scale is not None else 1.0 / (D**0.5)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    sq_p, sk_p = _round_up(Sq, block_q), _round_up(Sk, block_k)
    nq, nk = sq_p // block_q, sk_p // block_k

    # lane-folded views (free reshapes), zero-padded to whole blocks
    qf = jnp.pad(q.reshape(B, Sq, H * D), ((0, 0), (0, sq_p - Sq), (0, 0)))
    kf = jnp.pad(k.reshape(B, Sk, KVH * D), ((0, 0), (0, sk_p - Sk), (0, 0)))
    vf = jnp.pad(v.reshape(B, Sk, KVH * D), ((0, 0), (0, sk_p - Sk), (0, 0)))

    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=nk,
        q_offset=q_offset,
        kv_len=Sk if sk_p != Sk else None,
    )
    q_spec = pl.BlockSpec((None, block_q, D), lambda b, h, qi, ki: (b, qi, h))
    kv_spec = pl.BlockSpec(
        (None, block_k, D), lambda b, h, qi, ki: (b, ki, h // group))
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :Sq].reshape(B, Sq, H, D)
