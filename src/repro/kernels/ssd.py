"""Mamba2 SSD (state-space dual) chunked-scan Pallas TPU kernel.

The SSD form turns the linear recurrence into chunk-local matmuls (MXU work)
plus a tiny inter-chunk recurrence. Grid (B, NH, NC) with the chunk axis
minor: the running state h (P x N, fp32) lives in VMEM scratch and is carried
across the sequential chunk iterations — the TPU-native replacement for the
CUDA warp-parallel scan of the original implementation.

Per chunk of length L (default 128):
  a        = dt * A                              (L,)       log-decay
  L[i,j]   = exp(sum_{j<k<=i} a_k) (i>=j)        (L,L)
  scores   = (C B^T) * L                         (L,L)      MXU
  y_intra  = scores @ (dt * x)                   (L,P)      MXU
  y_inter  = (C * exp(cum_a)) @ h^T              (L,P)      MXU
  h       <- exp(tot_a) h + x^T @ (B * dt * exp(tot_a - cum_a))   (P,N) MXU
  y        = y_intra + y_inter + D * x

Layouts: x (B,S,NH,P); dt (B,S,NH); A,D (NH,); Bm,Cm (B,S,G,N). The
wrapper moves heads ahead of the sequence, x (B,NH,S,P) and Bm/Cm (B,G,S,N),
so each tile is a whole (chunk, P) / (chunk, N) slab — Mosaic needs a tile's
last two dims divisible by (8, 128) or equal to the array's, and P = 64 is
common. dt arrives twice, as a (chunk, 1) column and a (1, chunk) row, so the
in-chunk prefix sums are masked reductions instead of a cumsum or an
in-kernel transpose. A and D are per-head scalars in SMEM.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    a_ref,  # scalar prefetch (NH,) f32: A
    d_ref,  # scalar prefetch (NH,) f32: D
    x_ref,  # (L, P)
    dtc_ref,  # (L, 1)
    dtr_ref,  # (1, L)
    b_ref,  # (L, N)
    c_ref,  # (L, N)
    y_ref,
    hout_ref,
    h_ref,  # scratch (P, N) fp32
    *,
    chunk: int,
    num_chunks: int,
):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[hi]
    D = d_ref[hi]
    x = x_ref[...].astype(jnp.float32)  # (L, P)
    dt_col = dtc_ref[...].astype(jnp.float32)  # (L, 1)
    dt_row = dtr_ref[...].astype(jnp.float32)  # (1, L)
    Bm = b_ref[...].astype(jnp.float32)  # (L, N)
    Cm = c_ref[...].astype(jnp.float32)  # (L, N)

    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = li >= lj
    # inclusive prefix sums of the log-decay a = dt * A, as a column and a row
    a_cum_col = jnp.sum(jnp.where(lower, dt_row * A, 0.0), axis=1,
                        keepdims=True)  # (L, 1)
    a_cum_row = jnp.sum(jnp.where(li <= lj, dt_col * A, 0.0), axis=0,
                        keepdims=True)  # (1, L)
    a_tot = jnp.sum(dt_row * A, axis=1, keepdims=True)  # (1, 1)

    # intra-chunk
    seg = a_cum_col - a_cum_row  # sum_{j<k<=i}
    Lmat = jnp.where(lower, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    gated = scores * Lmat
    y_intra = jax.lax.dot_general(
        gated, dt_col * x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # inter-chunk: contribution of incoming state
    h = h_ref[...]  # (P, N)
    c_dec = Cm * jnp.exp(a_cum_col)  # (L, N)
    y_inter = jax.lax.dot_general(
        c_dec, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, P)

    # state update
    w = (dt_col * jnp.exp(a_tot - a_cum_col)) * Bm  # (L, N)
    s_new = jax.lax.dot_general(
        x, w, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, N)
    h_ref[...] = jnp.exp(a_tot) * h + s_new

    y = y_intra + y_inter + D * x
    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _emit_state():
        hout_ref[...] = h_ref[...]


def ssd(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    Cm: jax.Array,
    D: jax.Array,
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,NH,P), final state (B,NH,P,N) fp32)."""
    b, s, nh, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    assert nh % g == 0
    rep = nh // g
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    xh = jnp.swapaxes(x, 1, 2)  # (B, NH, S, P)
    dth = jnp.swapaxes(dt, 1, 2)  # (B, NH, S)
    kernel = functools.partial(_kernel, chunk=chunk, num_chunks=nc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((None, None, chunk, p),
                         lambda bi, hi, ci, *_: (bi, hi, ci, 0)),
            pl.BlockSpec((None, None, chunk, 1),
                         lambda bi, hi, ci, *_: (bi, hi, ci, 0)),
            pl.BlockSpec((None, None, 1, chunk),
                         lambda bi, hi, ci, *_: (bi, hi, 0, ci)),
            pl.BlockSpec((None, None, chunk, n),
                         lambda bi, hi, ci, *_: (bi, hi // rep, ci, 0)),
            pl.BlockSpec((None, None, chunk, n),
                         lambda bi, hi, ci, *_: (bi, hi // rep, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, p),
                         lambda bi, hi, ci, *_: (bi, hi, ci, 0)),
            pl.BlockSpec((None, None, p, n),
                         lambda bi, hi, ci, *_: (bi, hi, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
    )
    y, h = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, nh, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(A.astype(jnp.float32), D.astype(jnp.float32), xh, dth[..., None],
      dth[:, :, None, :], jnp.swapaxes(Bm, 1, 2), jnp.swapaxes(Cm, 1, 2))
    return jnp.swapaxes(y, 1, 2), h
