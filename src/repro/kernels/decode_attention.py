"""Flash-decode Pallas TPU kernel (serve_step path).

One new token per sequence attends to a long (possibly partially filled,
possibly sequence-sharded) KV cache. Grid (B, KVH, nS) with the S axis minor;
all H//KVH query heads sharing a kv head are processed together so the
(group x block_s) logits matmul has some MXU utilisation. Emits (o, lse) so
that shards of a sequence-sharded cache can be combined exactly with
``ref.combine_decode_shards`` across the `model` mesh axis.

cache_len is a scalar-prefetch operand ((B,) int32): number of valid slots
per sequence; ``pos_offset`` is the absolute position of local cache slot 0.

Layouts: the kernels read K/V lane-folded, (…, S, KVH*D), so each K/V tile
is a (block_s, D) slab picked by the kv-head index along the last axis; the
wrappers view a (B, S, KVH, D) cache or (P, page_size, KVH, D) pool so. The
dense kernel also reads a decode loop's stacked arena (N, B, S, KVH*D) in
place: the layer index is a second scalar-prefetch operand that the K/V
index map reads, so no layer slice or relayout is materialized. Mosaic
needs a tile's last two dims divisible by (sublanes, 128) or equal to the
array's, so compiled runs need ``D % 128 == 0`` and S blocks a multiple of
the dtype's sublane count (8 for f32, 16 for bf16, 32 for int8); interpret
mode takes any D.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import check_lane_width

NEG_INF = -1e30
LANES = 128


def _pick_block_s(S: int, want: int, align: int = 1) -> int:
    """Largest divisor of ``S`` that is <= ``want`` and a multiple of
    ``align``; ``S`` itself when there is none (a whole-axis block is always
    a legal tile).

    Arena widths are not always powers of two (prompt_len + max_new from a
    workload spec, e.g. S=160); asserting divisibility made those shapes hard
    failures. Falling back to the largest divisor keeps the grid exact —
    every position is covered exactly once, no padding tile."""
    bs = max(1, min(want, S))
    while bs and (S % bs or bs % align):
        bs -= 1
    return bs or S


def sublanes(dtype) -> int:
    """Rows of one native TPU tile for ``dtype``: 8 x 32-bit, 16 x 16-bit,
    32 x 8-bit."""
    return 32 // jnp.dtype(dtype).itemsize


def _ragged_block_index(si, lens_b, *, block_s: int, num_blocks: int,
                        pos_offset: int, window):
    """Clamp the S-block index for the ragged fetch-skip.

    The grid sweeps ``si = 0..num_blocks-1`` (minor axis) for every
    (sequence, kv-head) cell, but a slot with ``kv_len`` valid positions
    only *needs* blocks ``first..last``:

      last  = ceil((kv_len - pos_offset) / block_s) - 1       (tail cutoff)
      first = (kv_len - window - pos_offset) // block_s       (SWA head cutoff)

    Dead steps clamp to the nearest needed block, so consecutive grid steps
    map to the *same* block index and Pallas elides the K/V copy entirely —
    per-slot grid truncation via the scalar-prefetch lane, not just in-kernel
    masking of a full sweep. The clamped sequence is monotone, so every
    needed block is still fetched exactly once, and the compute-side
    ``pl.when(needed)`` guard (unchanged) skips the dead steps' math."""
    last = (lens_b - pos_offset + block_s - 1) // block_s - 1
    last = jnp.clip(last, 0, num_blocks - 1)
    si_c = jnp.minimum(si, last)
    if window is not None:
        first = jnp.clip((lens_b - window - pos_offset) // block_s,
                         0, num_blocks - 1)
        si_c = jnp.maximum(si_c, first)
    return si_c


def _kernel(
    len_ref,  # scalar prefetch (B,) int32
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    block_s: int,
    num_s_blocks: int,
    pos_offset: int,
    window: Optional[int],
    group: int,
):
    b = pl.program_id(0)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cache_len = len_ref[b]
    blk_lo = si * block_s + pos_offset
    # skip blocks entirely beyond the valid region (or before the window)
    needed = blk_lo < cache_len
    if window is not None:
        needed &= (blk_lo + block_s) > (cache_len - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[...]  # (group, D)
        k = k_ref[...]  # (block_s, D)
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale  # (group, block_s)
        kpos = blk_lo + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
        valid = kpos < cache_len
        if window is not None:
            valid &= kpos > (cache_len - 1) - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(si == num_s_blocks - 1)
    def _finalize():
        _emit(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _emit(o_ref, lse_ref, acc_ref, m_ref, l_ref):
    l = jnp.maximum(l_ref[:, :1], 1e-20)
    o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
    lse_ref[...] = m_ref[:, :1] + jnp.log(l)  # (group, 1)


def _quant_kernel(
    len_ref,  # scalar prefetch (B,) int32
    q_ref,
    k_ref,  # int8 tile
    v_ref,  # int8 tile
    ks_ref,  # f32 per-slot-per-head scales
    vs_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    block_s: int,
    num_s_blocks: int,
    pos_offset: int,
    window: Optional[int],
    group: int,
):
    """Flash-decode over an int8 KV cache: dequantization is fused into the
    tile loop (int8 tile + f32 scales dequantized in VMEM right before the
    logits matmul), so the full-width bf16 cache never exists in HBM — the
    whole point of ``ModelConfig.kv_quant``. Math otherwise identical to
    :func:`_kernel`."""
    b = pl.program_id(0)
    kh = pl.program_id(1)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cache_len = len_ref[b]
    blk_lo = si * block_s + pos_offset
    needed = blk_lo < cache_len
    if window is not None:
        needed &= (blk_lo + block_s) > (cache_len - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[...]  # (group, D)
        # the scale tiles hold every kv head, (block_s, KVH): pick this
        # head's column with a one-hot lane reduce
        lane = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape, 1)

        def head_col(ref):
            return jnp.sum(jnp.where(lane == kh, ref[...], 0.0), axis=1,
                           keepdims=True)

        # fused per-tile dequant: (block_s, D) int8 * (block_s, 1) f32
        k = k_ref[...].astype(jnp.float32) * head_col(ks_ref)
        v = v_ref[...].astype(jnp.float32) * head_col(vs_ref)
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s * scale  # (group, block_s)
        kpos = blk_lo + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
        valid = kpos < cache_len
        if window is not None:
            valid &= kpos > (cache_len - 1) - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(si == num_s_blocks - 1)
    def _finalize():
        _emit(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _decode_call(kernel, *, grid, num_scalar_prefetch, q_map, kv_specs,
                 B, KVH, group, D, dtype, interpret):
    """pallas_call shared by the three decode kernels: q and o are
    (B, KVH, group, D) tiles of ``group`` heads, lse is (B, KVH, group, 1),
    and the online-softmax state lives in VMEM scratch."""
    q_spec = pl.BlockSpec((None, None, group, D), q_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=grid,
        in_specs=[q_spec] + kv_specs,
        out_specs=[q_spec, pl.BlockSpec((None, None, group, 1), q_map)],
        scratch_shapes=[
            pltpu.VMEM((group, D), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, KVH, group, D), dtype),
            jax.ShapeDtypeStruct((B, KVH, group, 1), jnp.float32),
        ],
        interpret=interpret,
    )

    def run(*args):
        o, lse = call(*args)
        return o.reshape(B, KVH * group, D), lse.reshape(B, KVH * group)

    return run


def decode_attention_quant(
    q: jax.Array,
    k: jax.Array,  # (B, S, KVH, D) int8
    v: jax.Array,  # (B, S, KVH, D) int8
    k_scale: jax.Array,  # (B, S, KVH) f32
    v_scale: jax.Array,  # (B, S, KVH) f32
    cache_len: jax.Array,
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    pos_offset: int = 0,
    block_s: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Flash-decode over a quantized cache; returns (o (B,H,D), lse (B,H)).

    Equivalent to ``dequant_kv`` + :func:`decode_attention` but the cache
    stays int8 end-to-end in HBM (the previous ``_decode_quant`` model path
    materialized the full bf16 cache every decode step)."""
    B, H, D = q.shape
    _, S, KVH, _ = k.shape
    assert H % KVH == 0
    check_lane_width(D, interpret)
    group = H // KVH
    scale = scale if scale is not None else 1.0 / (D**0.5)
    block_s = _pick_block_s(S, block_s, sublanes(k.dtype))
    ns = S // block_s

    kernel = functools.partial(
        _quant_kernel,
        scale=scale,
        block_s=block_s,
        num_s_blocks=ns,
        pos_offset=pos_offset,
        window=window,
        group=group,
    )
    ragged = functools.partial(
        _ragged_block_index, block_s=block_s, num_blocks=ns,
        pos_offset=pos_offset, window=window,
    )
    kv_spec = pl.BlockSpec(
        (None, block_s, D), lambda b, kh, si, lens: (b, ragged(si, lens[b]), kh))
    sc_spec = pl.BlockSpec(
        (None, block_s, KVH), lambda b, kh, si, lens: (b, ragged(si, lens[b]), 0))
    run = _decode_call(
        kernel, grid=(B, KVH, ns), num_scalar_prefetch=1,
        q_map=lambda b, kh, si, lens: (b, kh, 0, 0),
        kv_specs=[kv_spec, kv_spec, sc_spec, sc_spec],
        B=B, KVH=KVH, group=group, D=D, dtype=q.dtype, interpret=interpret)
    return run(cache_len.astype(jnp.int32), q.reshape(B, KVH, group, D),
               k.reshape(B, S, KVH * D), v.reshape(B, S, KVH * D),
               k_scale, v_scale)


def _arena_kernel(len_ref, layer_ref, *refs, **kw):
    del layer_ref  # read by the K/V index map
    _kernel(len_ref, *refs, **kw)


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cache_len: jax.Array,
    *,
    layer: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    pos_offset: int = 0,
    block_s: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (o (B,H,D), lse (B,H)).

    ``k``/``v`` are one layer's cache, (B, S, KVH, D), or, with ``layer``
    (an int32 scalar, traced or not), a stacked lane-folded arena
    (N, B, S, KVH*D) of which layer ``layer`` is read."""
    B, H, D = q.shape
    if layer is None:
        _, S, KVH, _ = k.shape
        k, v = (a.reshape(1, B, S, KVH * D) for a in (k, v))
        layer = 0
    else:
        S, KVH = k.shape[2], k.shape[3] // D
    assert H % KVH == 0
    check_lane_width(D, interpret)
    group = H // KVH
    scale = scale if scale is not None else 1.0 / (D**0.5)
    block_s = _pick_block_s(S, block_s, sublanes(k.dtype))
    ns = S // block_s

    kernel = functools.partial(
        _arena_kernel,
        scale=scale,
        block_s=block_s,
        num_s_blocks=ns,
        pos_offset=pos_offset,
        window=window,
        group=group,
    )
    ragged = functools.partial(
        _ragged_block_index, block_s=block_s, num_blocks=ns,
        pos_offset=pos_offset, window=window,
    )
    kv_spec = pl.BlockSpec(
        (None, None, block_s, D),
        lambda b, kh, si, lens, n: (n[0], b, ragged(si, lens[b]), kh))
    run = _decode_call(
        kernel, grid=(B, KVH, ns), num_scalar_prefetch=2,
        q_map=lambda b, kh, si, lens, n: (b, kh, 0, 0),
        kv_specs=[kv_spec, kv_spec],
        B=B, KVH=KVH, group=group, D=D, dtype=q.dtype, interpret=interpret)
    # q heads are kv-major contiguous: (B, H, D) -> (B, KVH, group, D)
    return run(cache_len.astype(jnp.int32),
               jnp.reshape(layer, (1,)).astype(jnp.int32),
               q.reshape(B, KVH, group, D), k, v)


def _paged_kernel(
    len_ref,  # scalar prefetch (B,) int32
    tbl_ref,  # scalar prefetch (B, T) int32 block tables (unused in body:
    #           pages are resolved in the BlockSpec index_map)
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    block_s: int,
    num_s_blocks: int,
    group: int,
):
    del tbl_ref
    _kernel(
        len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
        scale=scale, block_s=block_s, num_s_blocks=num_s_blocks,
        pos_offset=0, window=None, group=group,
    )


def paged_decode_attention(
    q: jax.Array,  # (B, H, D)
    pool_k: jax.Array,  # (P, page_size, KVH, D) page pool
    pool_v: jax.Array,  # (P, page_size, KVH, D)
    tables: jax.Array,  # (B, T) int32 page ids; logical position p lives in
    #                     pool page tables[b, p // page_size] at offset
    #                     p % page_size
    kv_len: jax.Array,  # (B,) int32 valid positions per sequence
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Flash decode straight through a block table (serving's paged arena).

    Grid (B, KVH, T) with the page axis minor; each step's K/V tile is ONE
    pool page, picked by the BlockSpec index_map from the scalar-prefetched
    block table — the kernel never sees a contiguous cache, so the serving
    engine's page-staging copy (pool -> slot rows before every burst)
    disappears. Dead steps (``ti`` past ``ceil(kv_len / page_size)``) clamp
    the table lookup to the last live page: the same fetch-skip trick as
    :func:`_ragged_block_index`, on table entries instead of raw block
    indices. Table rows of finished/inactive lanes may point anywhere inside
    the pool — the in-kernel ``kpos < kv_len`` mask zeroes their
    contribution, so the outputs of those lanes are well-defined garbage the
    caller discards. Returns (o (B,H,D), lse (B,H))."""
    B, H, D = q.shape
    P, ps, KVH, _ = pool_k.shape
    T = tables.shape[1]
    assert H % KVH == 0
    check_lane_width(D, interpret)
    group = H // KVH
    scale = scale if scale is not None else 1.0 / (D**0.5)

    kernel = functools.partial(
        _paged_kernel,
        scale=scale,
        block_s=ps,
        num_s_blocks=T,
        group=group,
    )

    def kv_map(b, kh, ti, lens, tbl):
        last = jnp.clip((lens[b] + ps - 1) // ps - 1, 0, T - 1)
        return (tbl[b, jnp.minimum(ti, last)], 0, kh)

    kv_spec = pl.BlockSpec((None, ps, D), kv_map)
    run = _decode_call(
        kernel, grid=(B, KVH, T), num_scalar_prefetch=2,
        q_map=lambda b, kh, ti, lens, tbl: (b, kh, 0, 0),
        kv_specs=[kv_spec, kv_spec],
        B=B, KVH=KVH, group=group, D=D, dtype=q.dtype, interpret=interpret)
    return run(kv_len.astype(jnp.int32), tables.astype(jnp.int32),
               q.reshape(B, KVH, group, D), pool_k.reshape(P, ps, KVH * D),
               pool_v.reshape(P, ps, KVH * D))
