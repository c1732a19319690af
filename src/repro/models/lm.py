"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

Layer stacks are *pattern-compressed*: the per-layer kind sequence
(cfg.layer_kinds()) is reduced to its smallest repeating pattern P, params are
stacked over the N = num_layers / P repetitions, and the forward pass is a
``lax.scan`` over the N groups with the P positions unrolled inside the body.
Homogeneous archs get P=1 (pure scan over layers, e.g. 95-layer deepseek);
jamba gets P=8 / N=4. This keeps compile time and HLO size flat in depth —
essential when lowering for 512 devices.

Three execution modes share one backbone:
  full     — whole sequence, no cache (training loss / RL logprobs)
  prefill  — whole sequence, emits decode caches
  decode   — one token per sequence against the caches

Decode caches (per pattern position, stacked over groups):
  attn  {"k","v"} (N,B,W,KVH*hd) bf16, lane-folded as the decode kernel reads
        them — W = min(Smax, sliding_window): SWA archs get a ring buffer
        bounded at the window (the long_500k enabler for mixtral); with
        ``kv_quant`` int8 (N,B,W,KVH,hd) plus f32 scales (N,B,W,KVH)
  ssm   {"ssm","conv_x","conv_bc"} — constant-size Mamba2 state

Decode carries the whole cache tree through the layer loop and updates it in
place: layer n's new K/V row of each sequence is written at
[n, b, cache_len[b]] and the decode kernel reads layer n of the stacked
arena, so a step moves one row per sequence, not the arena.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.constraint import constrain, residual_entries, tp_shards
from repro.kernels import ops
from repro.models import layers, moe, ssm

Params = Dict[str, Any]

LOSS_CHUNK = 1024  # sequence chunking for the CE/logprob loss (memory bound)
IGNORE = -1  # label id excluded from the loss


# --------------------------------------------------------------------------- #
# pattern compression
# --------------------------------------------------------------------------- #
def pattern_length(cfg: ModelConfig) -> int:
    kinds = cfg.layer_kinds()
    L = len(kinds)
    for p in range(1, L + 1):
        if L % p == 0 and all(kinds[i] == kinds[i % p] for i in range(L)):
            return p
    return L


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #
def _init_block_pos(cfg: ModelConfig, key, kind: Tuple[str, str]) -> Params:
    mixer_kind, mlp_kind = kind
    ks = jax.random.split(key, 4)
    p: Params = {"norm1": layers.init_norm(cfg)}
    if mixer_kind == "attn":
        p["attn"] = layers.init_attention(cfg, ks[0])
    else:
        p["ssm"] = ssm.init_ssm(cfg, ks[0])
    if mlp_kind != "none" and not cfg.parallel_block:
        p["norm2"] = layers.init_norm(cfg)
    if mlp_kind == "dense":
        p["mlp"] = layers.init_mlp(cfg, ks[1])
    elif mlp_kind == "moe":
        p["moe"] = moe.init_moe(cfg, ks[1])
    return p


def init(cfg: ModelConfig, key) -> Params:
    P = pattern_length(cfg)
    N = cfg.num_layers // P
    kinds = cfg.layer_kinds()[:P]
    ks = jax.random.split(key, P + 2)

    blocks: List[Params] = []
    for pos in range(P):
        group_keys = jax.random.split(ks[pos], N)
        blocks.append(jax.vmap(lambda k: _init_block_pos(cfg, k, kinds[pos]))(group_keys))

    v, d = cfg.padded_vocab, cfg.d_model
    params: Params = {
        "embed": (jax.random.normal(ks[P], (v, d), jnp.float32) * 0.02).astype(
            jnp.bfloat16
        ),
        "blocks": blocks,
        "final_norm": layers.init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(ks[P + 1], (d, v), jnp.float32) / (d**0.5)
        ).astype(jnp.bfloat16)
    return params


# --------------------------------------------------------------------------- #
# mixers with cache plumbing
# --------------------------------------------------------------------------- #
def quant_kv(x: jax.Array):
    """(…, KVH, hd) -> (int8 values, f32 scales over the hd dim)."""
    m = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(m, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequant_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(jnp.bfloat16)


def _fold(x: jax.Array) -> jax.Array:
    """(…, KVH, hd) -> the cache's lane-folded (…, KVH*hd)."""
    return x.reshape(*x.shape[:-2], -1)


def _unfold(x: jax.Array, hd: int) -> jax.Array:
    """Lane-folded (…, KVH*hd) -> (…, KVH, hd)."""
    return x.reshape(*x.shape[:-1], -1, hd)


def _per_layer(step, cache: Params, layer):
    """Decode for the layouts read one layer at a time (int8 caches, the
    paged pool, SSM state): ``step`` maps layer ``layer`` of the stacked
    ``cache`` to (output, new layer), which is written back in place."""
    take = lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
    out, new = step(jax.tree.map(take, cache))
    return out, jax.tree.map(
        lambda a, x: jax.lax.dynamic_update_index_in_dim(a, x, layer, 0),
        cache, new)


def _write_rows(arena: jax.Array, layer, slot: jax.Array,
                rows: jax.Array) -> jax.Array:
    """Write sequence b's new K or V row ``rows[b]`` at ``[layer, b,
    slot[b]]`` of the stacked arena (N, B, W, F).

    An indexed update of B rows, done in place on the decode loop's carried
    buffer; its batch indices are an iota, so GSPMD updates a batch-sharded
    arena shard by shard. Where the W axis is sharded over `model` a scatter at a traced
    position made GSPMD all-gather the cache every step (§Perf A-it2), so
    there the layer is rewritten through an elementwise select instead: one
    read and write of the layer in HBM, no wire traffic."""
    rows = rows.astype(arena.dtype)
    B, W = arena.shape[1], arena.shape[2]
    if tp_shards(W) == 1:
        return arena.at[layer, jnp.arange(B), slot].set(rows)
    sel = (jax.lax.broadcasted_iota(jnp.int32, (B, W), 1)
           == slot[:, None])[..., None]
    cur = jax.lax.dynamic_index_in_dim(arena, layer, 0, keepdims=False)
    return jax.lax.dynamic_update_index_in_dim(
        arena, jnp.where(sel, rows[:, None], cur), layer, 0)


def _ring_width(cfg: ModelConfig, smax: int) -> int:
    if cfg.sliding_window is not None:
        return min(smax, cfg.sliding_window)
    return smax


def _attn_mixer(
    cfg: ModelConfig,
    p: Params,
    h: jax.Array,
    positions: jax.Array,
    mode: str,
    cache: Optional[Params],
    cache_len: Optional[jax.Array],
    smax: int,
    chunk_offset: Optional[int] = None,
    page_tables: Optional[jax.Array] = None,
    write_enable: Optional[jax.Array] = None,
    layer=None,
):
    if mode == "full":
        return layers.self_attention(cfg, p, h, positions), None

    if mode == "prefill_chunk":
        # one chunk of a chunked prefill: write this chunk's K/V into the
        # existing cache at [offset, offset+C) and attend the chunk's queries
        # against the (static-width) prefix [0, offset+C). ``chunk_offset``
        # is a Python int, so every slice below is static. Ring (SWA-bounded)
        # caches are unsupported — the engine falls back to whole-prompt
        # prefill for those archs.
        assert cache is not None and chunk_offset is not None
        C = h.shape[1]
        pos = (chunk_offset + jnp.arange(C))[None, :]
        q, k, v = layers.qkv_proj(cfg, p, h, pos)
        hi = chunk_offset + C
        if cfg.kv_quant:
            kq, vq = cache["k"], cache["v"]
            ks, vs = cache["k_scale"], cache["v_scale"]
            assert hi <= kq.shape[1], "chunked prefill past the cache width"
            kq_new, ks_new = quant_kv(k)
            vq_new, vs_new = quant_kv(v)
            kq = jax.lax.dynamic_update_slice(kq, kq_new, (0, chunk_offset, 0, 0))
            vq = jax.lax.dynamic_update_slice(vq, vq_new, (0, chunk_offset, 0, 0))
            ks = jax.lax.dynamic_update_slice(ks, ks_new, (0, chunk_offset, 0))
            vs = jax.lax.dynamic_update_slice(vs, vs_new, (0, chunk_offset, 0))
            o = ops.flash_attention(
                q, dequant_kv(kq[:, :hi], ks[:, :hi]),
                dequant_kv(vq[:, :hi], vs[:, :hi]),
                causal=True, window=cfg.sliding_window, q_offset=chunk_offset,
            )
            return layers.out_proj(cfg, p, o), {
                "k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        kc, vc = cache["k"], cache["v"]
        assert hi <= kc.shape[1], "chunked prefill past the cache width"
        kc = jax.lax.dynamic_update_slice(
            kc, _fold(k).astype(kc.dtype), (0, chunk_offset, 0))
        vc = jax.lax.dynamic_update_slice(
            vc, _fold(v).astype(vc.dtype), (0, chunk_offset, 0))
        hd = cfg.head_dim
        o = ops.flash_attention(
            q, _unfold(kc[:, :hi], hd), _unfold(vc[:, :hi], hd),
            causal=True, window=cfg.sliding_window, q_offset=chunk_offset,
        )
        return layers.out_proj(cfg, p, o), {"k": kc, "v": vc}

    if mode == "prefill":
        q, k, v = layers.qkv_proj(cfg, p, h, positions)
        o = ops.flash_attention(q, k, v, causal=True, window=cfg.sliding_window)
        B, S = h.shape[0], h.shape[1]
        W = _ring_width(cfg, smax)
        kvh, hd = cfg.num_kv_heads, cfg.head_dim
        kc = jnp.zeros((B, W, kvh, hd), k.dtype)
        vc = jnp.zeros((B, W, kvh, hd), v.dtype)
        if S >= W:  # keep the last W tokens (ring-aligned slots pos % W)
            slot = jnp.arange(S - W, S) % W
            kc = kc.at[:, slot].set(k[:, S - W :])
            vc = vc.at[:, slot].set(v[:, S - W :])
        else:
            kc = jax.lax.dynamic_update_slice(kc, k, (0, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, v, (0, 0, 0, 0))
        if cfg.kv_quant:
            kq, ks = quant_kv(kc)
            vq, vs = quant_kv(vc)
            return layers.out_proj(cfg, p, o), {
                "k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        return layers.out_proj(cfg, p, o), {"k": _fold(kc), "v": _fold(vc)}

    # decode: ``cache`` is this pattern position's stacked tree, ``layer``
    # the group the loop is at
    assert cache is not None and cache_len is not None
    q, k_new, v_new = layers.qkv_proj(cfg, p, h, cache_len[:, None])
    if page_tables is not None:
        return _per_layer(lambda c: _decode_paged(
            cfg, p, q, k_new, v_new, c, cache_len, page_tables,
            write_enable), cache, layer)
    if cfg.kv_quant:
        return _per_layer(lambda c: _decode_quant(
            cfg, p, q, k_new, v_new, c, cache_len), cache, layer)
    kc, vc = cache["k"], cache["v"]
    W = kc.shape[2]
    ring = cfg.sliding_window is not None and W <= cfg.sliding_window
    slot = cache_len % W if ring else cache_len
    kc = _write_rows(kc, layer, slot, _fold(k_new[:, 0]))
    vc = _write_rows(vc, layer, slot, _fold(v_new[:, 0]))
    # pin the arena to its resident layout (batch x seq-over-model)
    kc = constrain(kc, None, "dp", "tp", None)
    vc = constrain(vc, None, "dp", "tp", None)
    if ring:
        lens, window = jnp.minimum(cache_len + 1, W), None
    else:
        lens, window = cache_len + 1, cfg.sliding_window
    o, _ = ops.decode_attention(q[:, 0], kc, vc, lens, window=window,
                                layer=layer)
    return layers.out_proj(cfg, p, o)[:, None], {"k": kc, "v": vc}


def _decode_quant(cfg, p, q, k_new, v_new, cache, cache_len):
    """int8-cache decode step: quantize the new slot and attend with the
    fused int8 decode kernel (``ops.decode_attention_quant``) — the cache
    stays int8 in HBM; dequantization happens per tile inside the kernel
    (the ref path dequantizes up front, bitwise-identical to the pre-fusion
    full-cache dequantize)."""
    B = q.shape[0]
    kq, vq = cache["k"], cache["v"]
    ks, vs = cache["k_scale"], cache["v_scale"]
    W = kq.shape[1]
    ring = cfg.sliding_window is not None and W <= cfg.sliding_window
    slot = cache_len % W if ring else cache_len
    kq_new, ks_new = quant_kv(k_new[:, 0])
    vq_new, vs_new = quant_kv(v_new[:, 0])
    sel = (jax.lax.broadcasted_iota(jnp.int32, (B, W), 1)
           == slot[:, None])
    sel4 = sel[..., None, None]
    kq = jnp.where(sel4, kq_new[:, None], kq)
    vq = jnp.where(sel4, vq_new[:, None], vq)
    ks = jnp.where(sel[..., None], ks_new[:, None], ks)
    vs = jnp.where(sel[..., None], vs_new[:, None], vs)
    if ring:
        eff_len = jnp.minimum(cache_len + 1, W)
        o, _ = ops.decode_attention_quant(
            q[:, 0], kq, vq, ks, vs, eff_len, window=None)
    else:
        o, _ = ops.decode_attention_quant(
            q[:, 0], kq, vq, ks, vs, cache_len + 1, window=cfg.sliding_window)
    new_cache = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    return layers.out_proj(cfg, p, o)[:, None], new_cache


def _decode_paged(cfg, p, q, k_new, v_new, cache, cache_len, page_tables,
                  write_enable):
    """Paged decode step: the cache leaves ARE the serving engine's shared
    page pool (``(num_pages, page_size, kvh, hd)``); each lane's KV lives in
    the pages its ``page_tables`` row names. The new token's K/V is written
    straight into the lane's current page (no staging rows), and the paged
    flash-decode kernel gathers pages through the table — the burst never
    materializes contiguous per-slot KV.

    ``write_enable`` (bool (B,), optional) routes retired lanes' writes to
    an out-of-range page that ``mode="drop"`` discards: a finished slot can
    keep stepping in the fixed-shape burst without corrupting pool pages it
    no longer owns. Attention-only, full-window, bf16 caches (the serving
    engine's admission gate); SWA rings and int8 pools are rejected here."""
    assert cfg.sliding_window is None, "paged decode: SWA unsupported"
    assert not cfg.kv_quant, "paged decode: int8 pool unsupported"
    kc, vc = cache["k"], cache["v"]  # (P, page_size, KVH*hd), lane-folded
    P, ps = kc.shape[0], kc.shape[1]
    T = page_tables.shape[1]
    pidx = jnp.clip(cache_len // ps, 0, T - 1)
    page = jnp.take_along_axis(page_tables, pidx[:, None], axis=1)[:, 0]
    off = cache_len % ps
    if write_enable is not None:
        page = jnp.where(write_enable, page, P)  # OOB -> dropped below
    kc = kc.at[page, off].set(_fold(k_new[:, 0]).astype(kc.dtype), mode="drop")
    vc = vc.at[page, off].set(_fold(v_new[:, 0]).astype(vc.dtype), mode="drop")
    hd = cfg.head_dim
    o, _ = ops.paged_decode_attention(q[:, 0], _unfold(kc, hd),
                                      _unfold(vc, hd), page_tables,
                                      cache_len + 1)
    return layers.out_proj(cfg, p, o)[:, None], {"k": kc, "v": vc}


def _ssm_mixer(cfg, p, h, mode, cache, layer=None):
    if mode == "prefill_chunk":
        raise NotImplementedError(
            "chunked prefill needs SSM state carried between chunks; "
            "use whole-prompt prefill (prefill_chunk=0) for SSM/hybrid archs"
        )
    if mode == "full":
        return ssm.apply_ssm(cfg, p, h), None
    if mode == "prefill":
        out, state = ssm.apply_ssm(cfg, p, h, return_state=True)
        return out, state
    return _per_layer(lambda c: ssm.apply_ssm_decode(cfg, p, h, c), cache,
                      layer)


def _apply_block(
    cfg: ModelConfig,
    p: Params,
    kind: Tuple[str, str],
    h: jax.Array,
    positions: Optional[jax.Array],
    mode: str,
    cache: Optional[Params],
    cache_len: Optional[jax.Array],
    smax: int,
    chunk_offset: Optional[int] = None,
    page_tables: Optional[jax.Array] = None,
    write_enable: Optional[jax.Array] = None,
    layer=None,
):
    mixer_kind, mlp_kind = kind
    # each sub-layer's residual add sits in its scope, so a matmul fused
    # with the add keeps the sub-layer's name in the device trace
    hn = layers.apply_norm(cfg, p["norm1"], h)
    with jax.named_scope("mixer"):
        if mixer_kind == "attn":
            mix_out, new_cache = _attn_mixer(
                cfg, p["attn"], hn, positions, mode, cache, cache_len, smax,
                chunk_offset, page_tables, write_enable, layer)
        else:
            assert page_tables is None, "paged decode: attention-only archs"
            mix_out, new_cache = _ssm_mixer(cfg, p["ssm"], hn, mode, cache,
                                            layer)
        if not cfg.parallel_block:
            h = h + mix_out

    aux = jnp.zeros((), jnp.float32)
    if cfg.parallel_block:
        with jax.named_scope("mlp"):
            if mlp_kind == "dense":
                mlp_out = layers.apply_mlp(cfg, p["mlp"], hn)
            elif mlp_kind == "moe":
                mlp_out, aux = moe.apply_moe(cfg, p["moe"], hn)
            else:
                mlp_out = 0.0
            return h + mix_out + mlp_out, aux, new_cache

    if mlp_kind != "none":
        hn2 = layers.apply_norm(cfg, p["norm2"], h)
        with jax.named_scope("mlp"):
            if mlp_kind == "dense":
                h = h + layers.apply_mlp(cfg, p["mlp"], hn2)
            else:
                mlp_out, aux = moe.apply_moe(cfg, p["moe"], hn2)
                h = h + mlp_out
    return h, aux, new_cache


# --------------------------------------------------------------------------- #
# backbone: scan over groups, pattern positions unrolled in the body
# --------------------------------------------------------------------------- #
def backbone(
    cfg: ModelConfig,
    params: Params,
    h: jax.Array,
    positions: Optional[jax.Array],
    *,
    mode: str = "full",
    caches: Optional[List[Any]] = None,
    cache_len: Optional[jax.Array] = None,
    smax: int = 0,
    remat: bool = False,
    unroll: bool = False,
    chunk_offset: Optional[int] = None,
    page_tables: Optional[jax.Array] = None,
    write_enable: Optional[jax.Array] = None,
):
    """Returns (h, aux_sum, new_caches).

    ``unroll=True`` replaces the layer-group scan with a Python loop: same
    math, explicit per-layer HLO. Used by the dry-run so cost_analysis()
    counts every layer (XLA prices a while-loop body once) — and by perf
    variants trading compile time for scheduling freedom.

    Decode carries the stacked caches through the loop whole, with the
    group index beside each group's parameters, and each block updates its
    layer in place; the other modes scan the caches as per-group slices."""
    P = pattern_length(cfg)
    N = cfg.num_layers // P
    kinds = cfg.layer_kinds()[:P]
    blocks = params["blocks"]  # list over positions, each stacked over groups
    decode = mode == "decode"

    def body(carry, xs):
        h, aux, carried = carry
        if decode:
            group_params, layer = xs
            group_caches = carried
        else:
            (group_params, group_caches), layer = xs, None
        new_caches = []
        for pos in range(P):
            c_in = None if group_caches is None else group_caches[pos]
            h, a, c_out = _apply_block(
                cfg, group_params[pos], kinds[pos],
                h, positions, mode, c_in, cache_len, smax, chunk_offset,
                page_tables, write_enable, layer,
            )
            # sequence-parallel residual stream (Megatron-SP): between
            # blocks the seq dim shards over `model`, so the out-proj's TP
            # all-reduce lowers to a reduce-scatter (+ all-gather at the next
            # block's QKV). REPRO_SP=0 restores the baseline arm.
            h = constrain(h, *residual_entries())
            aux = aux + a
            new_caches.append(c_out)
        if decode:
            return (h, aux, new_caches), None
        if all(c is None for c in new_caches):
            return (h, aux, None), None
        return (h, aux, None), new_caches

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)

    carry = (h, jnp.zeros((), jnp.float32), caches if decode else None)
    xs = (blocks, jnp.arange(N)) if decode else (blocks, caches)
    if unroll:
        ys = []
        for i in range(N):
            xs_i = (jax.tree.map(lambda t: t[i], blocks),
                    i if decode else jax.tree.map(lambda t: t[i], caches))
            carry, y = body(carry, xs_i)
            ys.append(y)
        if decode or ys[0] is None:
            ys = None
        else:
            ys = jax.tree.map(lambda *ts: jnp.stack(ts), *ys)
    else:
        carry, ys = jax.lax.scan(body, carry, xs)
    h, aux, carried = carry
    h = layers.apply_norm(cfg, params["final_norm"], h)
    return h, aux, carried if decode else ys


def embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    h = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model**0.5, h.dtype)
    return constrain(h, "dp", None, None)


def _head_matrix(cfg: ModelConfig, params: Params) -> jax.Array:
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def _head_logits(cfg: ModelConfig, params: Params, h: jax.Array) -> jax.Array:
    """(B, d) last hidden states -> (B, V) float32 logits, padded vocabulary
    slots masked."""
    with jax.named_scope("head"):
        logits = (h @ _head_matrix(cfg, params)).astype(jnp.float32)
        return mask_padded_vocab(cfg, logits)


def assemble_input(
    cfg: ModelConfig, params: Params, tokens: jax.Array,
    prefix_embeds: Optional[jax.Array],
) -> jax.Array:
    """Token embeddings, with modality prefix embeddings concatenated ahead
    (VLM patches / audio frames per the assignment's frontend stub)."""
    h = embed_tokens(cfg, params, tokens)
    if prefix_embeds is not None and cfg.num_prefix_embeds > 1:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    return h


# --------------------------------------------------------------------------- #
# chunked CE loss / logprobs (never materializes (B,S,V))
# --------------------------------------------------------------------------- #
def _chunked_head_scan(h, w_head, labels, chunk, vocab_size=None, unroll=False):
    """scan over sequence chunks; returns per-position (logprob, entropy, mask)."""
    B, S, d = h.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=IGNORE)
    nc = h.shape[1] // chunk
    hc = jnp.moveaxis(h.reshape(B, nc, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)
    vpad = w_head.shape[1]
    vmask = None
    if vocab_size is not None and vocab_size < vpad:
        vmask = jnp.arange(vpad) < vocab_size
    # gather the FSDP-sharded head once, keep it vocab-TP for the chunk loop
    w_head = constrain(w_head, None, "tp")

    @jax.checkpoint
    def body(_, xs):
        hx, lx = xs
        logits = (hx @ w_head).astype(jnp.float32)  # (B, chunk, V)
        logits = constrain(logits, "dp", None, "tp")
        if vmask is not None:  # exclude padded vocab slots (match sampling)
            logits = jnp.where(vmask, logits, -1e30)
        logz = jax.nn.logsumexp(logits, axis=-1)
        tok = jnp.take_along_axis(
            logits, jnp.maximum(lx, 0)[..., None], axis=-1
        )[..., 0]
        logprob = tok - logz
        probs = jax.nn.softmax(logits, axis=-1)
        entropy = logz - jnp.sum(probs * logits, axis=-1)
        return (), (logprob, entropy, (lx != IGNORE))

    if unroll:
        outs = [body((), (hc[i], lc[i]))[1] for i in range(nc)]
        lp, ent, mask = (jnp.stack(ts) for ts in zip(*outs))
    else:
        _, (lp, ent, mask) = jax.lax.scan(body, (), (hc, lc))
    fix = lambda t: jnp.moveaxis(t, 0, 1).reshape(B, -1)[:, :S]
    return fix(lp), fix(ent), fix(mask)


def token_stats(cfg, params, h, labels, chunk=LOSS_CHUNK, unroll=False):
    with jax.named_scope("head"):
        return _chunked_head_scan(
            h, _head_matrix(cfg, params), labels, chunk,
            vocab_size=cfg.vocab_size, unroll=unroll,
        )


def ce_loss(cfg, params, h, labels, unroll=False):
    lp, ent, mask = token_stats(cfg, params, h, labels, unroll=unroll)
    denom = jnp.maximum(jnp.sum(mask), 1)
    loss = -jnp.sum(lp * mask) / denom
    return loss, {"ce": loss, "entropy": jnp.sum(ent * mask) / denom}


# --------------------------------------------------------------------------- #
# public entry points
# --------------------------------------------------------------------------- #
def loss_fn(
    cfg: ModelConfig,
    params: Params,
    batch: Dict[str, jax.Array],
    *,
    remat: bool = True,
    unroll: bool = False,
):
    """LM training loss. batch: tokens (B,St) [, prefix_embeds (B,P,d)],
    labels (B, P+St) with IGNORE at non-predicted positions."""
    tokens = batch["tokens"]
    prefix = batch.get("prefix_embeds")
    h = assemble_input(cfg, params, tokens, prefix)
    positions = jnp.arange(h.shape[1])[None, :]
    h, aux, _ = backbone(cfg, params, h, positions, mode="full", remat=remat,
                         unroll=unroll)
    loss, metrics = ce_loss(cfg, params, h, batch["labels"], unroll=unroll)
    if cfg.num_experts:
        loss = loss + 0.01 * aux
        metrics["moe_aux"] = aux
    return loss, metrics


def logprobs_fn(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    *,
    prefix_embeds: Optional[jax.Array] = None,
    remat: bool = False,
    unroll: bool = False,
):
    """Per-token logprob + entropy of ``tokens`` under the model (RL eval).

    Returns (logprob, entropy) each (B, S): position i scores tokens[:, i]
    given tokens[:, :i] (position 0 gets 0)."""
    h = assemble_input(cfg, params, tokens, prefix_embeds)
    positions = jnp.arange(h.shape[1])[None, :]
    h, _, _ = backbone(cfg, params, h, positions, mode="full", remat=remat,
                       unroll=unroll)
    offset = h.shape[1] - tokens.shape[1]  # prefix length
    labels = tokens[:, 1:]
    h_pred = h[:, offset : offset + tokens.shape[1] - 1]
    lp, ent, _ = token_stats(cfg, params, h_pred, labels)
    zero = jnp.zeros((tokens.shape[0], 1), lp.dtype)
    return (
        jnp.concatenate([zero, lp], axis=1),
        jnp.concatenate([zero, ent], axis=1),
    )


def init_caches(cfg: ModelConfig, batch: int, smax: int):
    """Zero caches (one entry per pattern position, stacked over groups)."""
    P = pattern_length(cfg)
    N = cfg.num_layers // P
    kinds = cfg.layer_kinds()[:P]
    W = _ring_width(cfg, smax)
    caches = []
    for pos in range(P):
        if kinds[pos][0] == "attn":
            kvh, hd = cfg.num_kv_heads, cfg.head_dim
            if cfg.kv_quant:
                caches.append(
                    {
                        "k": jnp.zeros((N, batch, W, kvh, hd), jnp.int8),
                        "v": jnp.zeros((N, batch, W, kvh, hd), jnp.int8),
                        "k_scale": jnp.zeros((N, batch, W, kvh), jnp.float32),
                        "v_scale": jnp.zeros((N, batch, W, kvh), jnp.float32),
                    }
                )
            else:
                caches.append(
                    {
                        "k": jnp.zeros((N, batch, W, kvh * hd), jnp.bfloat16),
                        "v": jnp.zeros((N, batch, W, kvh * hd), jnp.bfloat16),
                    }
                )
        else:
            shapes = ssm.ssm_state_shapes(cfg, batch)
            caches.append(
                {k: jnp.zeros((N,) + s.shape, s.dtype) for k, s in shapes.items()}
            )
    return caches


def prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    *,
    smax: int,
    prefix_embeds: Optional[jax.Array] = None,
    unroll: bool = False,
):
    """Run the prompt, return (last-position logits, caches, cache_len)."""
    h = assemble_input(cfg, params, tokens, prefix_embeds)
    positions = jnp.arange(h.shape[1])[None, :]
    h, _, caches = backbone(
        cfg, params, h, positions, mode="prefill", smax=smax, unroll=unroll
    )
    logits = _head_logits(cfg, params, h[:, -1])
    cache_len = jnp.full((tokens.shape[0],), h.shape[1], jnp.int32)
    return logits, caches, cache_len


def _decode_hidden(
    cfg: ModelConfig,
    params: Params,
    token: jax.Array,  # (B,) or (B,1)
    caches,
    cache_len: jax.Array,  # (B,)
    *,
    unroll: bool = False,
    page_tables: Optional[jax.Array] = None,
    write_enable: Optional[jax.Array] = None,
):
    """Shared decode-step body: embed -> backbone -> last hidden (B, d).
    With ``page_tables``, ``caches`` is the serving page pool and attention
    runs through the block table (see :func:`_decode_paged`)."""
    token = token.reshape(-1, 1)
    h = embed_tokens(cfg, params, token)
    h, _, new_caches = backbone(
        cfg, params, h, None, mode="decode", caches=caches, cache_len=cache_len,
        smax=0, unroll=unroll, page_tables=page_tables,
        write_enable=write_enable,
    )
    return h[:, 0], new_caches


def decode_step(
    cfg: ModelConfig,
    params: Params,
    token: jax.Array,  # (B,) or (B,1)
    caches,
    cache_len: jax.Array,  # (B,)
    unroll: bool = False,
):
    """One decode step. Returns (logits (B,V), new_caches, cache_len+1)."""
    h, new_caches = _decode_hidden(
        cfg, params, token, caches, cache_len, unroll=unroll)
    return _head_logits(cfg, params, h), new_caches, cache_len + 1


def decode_step_sample(
    cfg: ModelConfig,
    params: Params,
    token: jax.Array,  # (B,) or (B,1)
    caches,
    cache_len: jax.Array,  # (B,)
    key: jax.Array,
    temperature: float,  # static; 0.0 = greedy
    *,
    top_p: float = 1.0,  # static; < 1.0 routes dispatch to the ref path
    unroll: bool = False,
):
    """One decode step with the sampler fused behind the kernel dispatch:
    the (B, padded_vocab) logits never leave the op (``ops.fused_sample``).
    Returns (sampled token (B,), behaviour logprob (B,) under the untempered
    masked distribution, new_caches, cache_len+1). The ref dispatch path is
    bitwise-identical to ``decode_step`` + ``rollout.sample_token`` +
    ``log_softmax`` gather."""
    h, new_caches = _decode_hidden(
        cfg, params, token, caches, cache_len, unroll=unroll)
    with jax.named_scope("head"):
        tok, lp = ops.fused_sample(
            h, _head_matrix(cfg, params), key, temperature,
            vocab_size=cfg.vocab_size, top_p=top_p,
        )
    return tok, lp, new_caches, cache_len + 1


def decode_step_paged(
    cfg: ModelConfig,
    params: Params,
    token: jax.Array,  # (B,) or (B,1)
    pool,  # init_caches(num_pages, page_size) tree — the shared page pool
    cache_len: jax.Array,  # (B,)
    page_tables: jax.Array,  # (B, T) int32 pool-page ids per lane
    *,
    write_enable: Optional[jax.Array] = None,  # bool (B,); False = retired
    unroll: bool = False,
):
    """Paged decode step over the serving page pool. Returns
    (logits (B,V), new_pool, cache_len+1)."""
    h, new_pool = _decode_hidden(
        cfg, params, token, pool, cache_len, unroll=unroll,
        page_tables=page_tables, write_enable=write_enable)
    return _head_logits(cfg, params, h), new_pool, cache_len + 1


def decode_step_paged_sample(
    cfg: ModelConfig,
    params: Params,
    token: jax.Array,  # (B,) or (B,1)
    pool,
    cache_len: jax.Array,  # (B,)
    page_tables: jax.Array,  # (B, T) int32
    keys: jax.Array,  # (B, 2) uint32 per-row PRNG keys
    temps: jax.Array,  # (B,) f32; <= 0 means greedy
    *,
    write_enable: Optional[jax.Array] = None,
    unroll: bool = False,
):
    """Paged decode + fused per-row sampling (the serving burst step).
    Returns (sampled token (B,), new_pool, cache_len+1)."""
    h, new_pool = _decode_hidden(
        cfg, params, token, pool, cache_len, unroll=unroll,
        page_tables=page_tables, write_enable=write_enable)
    with jax.named_scope("head"):
        tok = ops.fused_sample_rows(
            h, _head_matrix(cfg, params), keys, temps,
            vocab_size=cfg.vocab_size)
    return tok, new_pool, cache_len + 1


def prefill_chunk(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # (B, C) one chunk of the prompts
    caches,  # per-slot caches being filled (width >= offset + C)
    *,
    offset: int,  # static: absolute position of tokens[:, 0]
    unroll: bool = False,
):
    """One chunk of a chunked prefill into existing decode caches.

    The continuous-batching rollout engine uses this to break a refill
    prompt's prefill into bounded pieces (so a long prefill never stalls
    in-flight decodes for its full length): chunk c writes K/V into
    ``caches`` at ``[offset, offset+C)`` and attends against the prefix
    ``[0, offset+C)``. For bf16 caches, calling it over consecutive chunks
    is numerically equivalent to one whole-prompt :func:`prefill` (same
    masked softmax, up to float reassociation); with ``kv_quant`` the chunk
    attends its prefix's quantize->dequantized K/V, which whole-prompt
    prefill never does — the rollout engine excludes that combination.
    Returns (last-position logits, new caches);
    the caller owns ``cache_len`` (set it to the prompt length after the
    final chunk). Attention-only paths; SSM mixers raise (state would need
    to carry between chunks) and ring-bounded SWA caches are rejected by
    width asserts."""
    h = embed_tokens(cfg, params, tokens)
    h, _, new_caches = backbone(
        cfg, params, h, None, mode="prefill_chunk", caches=caches,
        chunk_offset=offset, unroll=unroll,
    )
    return _head_logits(cfg, params, h[:, -1]), new_caches


def gather_cache_rows(caches, slots: jax.Array):
    """Pull the per-slot cache rows at ``slots`` (batch axis 1 of every
    leaf: leaves are stacked (N, B, ...) over layer groups)."""
    return jax.tree.map(lambda a: jnp.take(a, slots, axis=1), caches)


def scatter_cache_rows(caches, rows, slots: jax.Array):
    """Slot-reset path: overwrite the arena's rows at ``slots`` with freshly
    prefilled ``rows`` (same tree structure, batch axis 1). Out-of-range
    slot ids are dropped — the engine pads refill batches to a fixed lane
    count and parks the padding lanes at an out-of-range slot."""
    return jax.tree.map(
        lambda a, r: a.at[:, slots].set(r.astype(a.dtype), mode="drop"),
        caches, rows,
    )


def gather_cache_pages(caches, slots: jax.Array, *, num_pages: int,
                       page_size: int):
    """Page-granular generalization of :func:`gather_cache_rows`: pull the
    first ``num_pages`` fixed-size KV pages (``page_size``-token spans along
    the token axis) of the rows at ``slots``. Leaves come back shaped
    ``(N, R, num_pages, page_size, *rest)`` — one block-table row per lane —
    ready to be stored into a page pool (``repro.serving.paged_arena``).

    Attention caches only: every leaf must carry the token axis at index 2
    (``(N, B, W, ...)``); SSM recurrent state has no token axis to page.
    """
    span = num_pages * page_size

    def g(a):
        rows = jnp.take(a, slots, axis=1)[:, :, :span]
        return rows.reshape(
            rows.shape[:2] + (num_pages, page_size) + rows.shape[3:])

    return jax.tree.map(g, caches)


def scatter_cache_pages(caches, pages, slots: jax.Array):
    """Inverse of :func:`gather_cache_pages`: write per-lane page stacks
    (leaves ``(N, R, k, page_size, *rest)``) contiguously into the arena
    rows at ``slots``, covering token positions ``[0, k * page_size)``.
    Out-of-range slot ids are dropped (padding lanes), mirroring
    :func:`scatter_cache_rows`."""

    def s(a, p):
        span = p.shape[2] * p.shape[3]
        flat = p.reshape(p.shape[:2] + (span,) + p.shape[4:])
        return a.at[:, slots, :span].set(flat.astype(a.dtype), mode="drop")

    return jax.tree.map(s, caches, pages)


def mask_padded_vocab(cfg: ModelConfig, logits: jax.Array) -> jax.Array:
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    v = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    return jnp.where(v, logits, -1e30)
