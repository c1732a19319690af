"""Sharding rules: FSDP x TP PartitionSpecs for every architecture.

Param rules are name-based over the last two dims (stacked leading dims —
layer groups, experts — are left-padded with None / FSDP as divisibility
allows). Conventions (DESIGN.md §6):

  * TP over `model`: attention heads (padded to 16), d_ff, SSD heads, vocab.
  * FSDP over (`pod`,`data`): the d_model-sized dim of every matrix, so
    params + optimizer state scale 1/(pod*data) — ZeRO-3 semantics.
  * GQA kv_heads < tp  -> K/V projections replicated over `model`
    (transient; the decode cache is SEQUENCE-sharded over `model` instead).
  * MoE expert dim (8/16/40, never 16-divisible) -> experts replicated over
    `model`, their f dim TP-sharded ("expert tensor parallelism").
  * batch < dp  (long_500k B=1) -> batch replicated, decode caches
    context-sharded over ALL axes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def _divisible(n: int, mesh: Mesh, axes) -> bool:
    if not axes:
        return True
    size = 1
    for a in axes if isinstance(axes, tuple) else (axes,):
        size *= mesh.shape[a]
    return n % size == 0


def _maybe(n: int, mesh: Mesh, axes):
    """axes if they divide n else None (replicate)."""
    return axes if _divisible(n, mesh, axes) else None


# --------------------------------------------------------------------------- #
# param specs
# --------------------------------------------------------------------------- #
def _base_rule(cfg: ModelConfig, mesh: Mesh, name: str, shape,
               mode: str = "train") -> Tuple:
    """PartitionSpec entries for the TRAILING dims that the rule understands;
    leading (stack) dims are padded by the caller.

    mode="serve": decode weights stay TP-sharded over `model` but REPLICATED
    over the data axes (no FSDP) — a decode step would otherwise all-gather
    the FSDP-sharded weights EVERY token (measured: 15.6 GB/step on
    deepseek-67b:decode_32k). Full-2D TP was tried first and REFUTED (44
    GB/step: the weights' data-axis sharding fights the batch's, §Perf
    A-it3a); replicated-over-data weights cost N*2/16 bytes of HBM per device
    and drop the per-step wire to tiny activation reductions. build_serve
    picks this mode only when the weights+cache fit the HBM budget."""
    if mode == "serve":
        tp_all = ("model",)
        if name == "embed":  # (V, d)
            return (_maybe(shape[0], mesh, tp_all), None)
        if name in ("w_q", "w_dt", "w_k", "w_v", "w_in", "w_gate", "w_z",
                    "w_x", "lm_head"):
            return (None, _maybe(shape[-1], mesh, tp_all))
        if name in ("w_o", "w_out"):
            return (_maybe(shape[-2], mesh, tp_all), None)
        if name in ("b_q", "b_k", "b_v", "b_in"):
            return (_maybe(shape[-1], mesh, tp_all),)
        if name in ("A_log", "D", "dt_bias", "norm_w"):
            return (_maybe(shape[-1], mesh, tp_all),)
        if name == "conv_x":
            return (None, _maybe(shape[-1], mesh, tp_all))
        if name in ("w_B", "w_C", "router", "conv_bc", "b_o", "b_out",
                    "w", "b", "v_head"):
            return tuple(None for _ in shape[-2:]) if len(shape) >= 2 else (None,)
        return tuple(None for _ in shape)

    fsdp = fsdp_axes(mesh)
    tp_ok_kv = cfg.num_kv_heads and _divisible(
        cfg.num_kv_heads, mesh, ("model",)
    )
    if name == "embed":  # (V, d)
        return (_maybe(shape[0], mesh, "model"), _maybe(shape[1], mesh, fsdp))
    if name == "lm_head":  # (d, V)
        return (_maybe(shape[-2], mesh, fsdp), _maybe(shape[-1], mesh, "model"))
    if name == "v_head":  # (d, 1)
        return (_maybe(shape[-2], mesh, fsdp), None)
    if name in ("w_q", "w_dt"):  # (d, Hp*hd) / (d, nh)
        return (_maybe(shape[-2], mesh, fsdp), _maybe(shape[-1], mesh, "model"))
    if name in ("w_k", "w_v"):  # (d, kvh*hd): TP only when kvh | tp
        tp = "model" if tp_ok_kv else None
        return (_maybe(shape[-2], mesh, fsdp), tp)
    if name == "w_o":  # (Hp*hd, d)
        return (_maybe(shape[-2], mesh, "model"), _maybe(shape[-1], mesh, fsdp))
    if name in ("w_in", "w_gate", "w_z", "w_x"):  # (d, f) / (d, din)
        return (_maybe(shape[-2], mesh, fsdp), _maybe(shape[-1], mesh, "model"))
    if name == "w_out":  # (f|din, d)
        return (_maybe(shape[-2], mesh, "model"), _maybe(shape[-1], mesh, fsdp))
    if name in ("w_B", "w_C"):  # (d, g*n): tiny -> replicate cols
        return (_maybe(shape[-2], mesh, fsdp), None)
    if name == "router":  # (d, E)
        return (_maybe(shape[-2], mesh, fsdp), None)
    if name == "conv_x":  # (kw, din)
        return (None, _maybe(shape[-1], mesh, "model"))
    if name == "conv_bc":
        return (None, None)
    if name in ("A_log", "D", "dt_bias", "norm_w"):  # (nh,) / (din,)
        return (_maybe(shape[-1], mesh, "model"),)
    if name in ("b_q", "b_in"):  # (Hp*hd,) / (f,)
        return (_maybe(shape[-1], mesh, "model"),)
    if name in ("b_k", "b_v"):
        return ("model" if tp_ok_kv and _divisible(shape[-1], mesh, "model") else None,)
    if name in ("b_o", "b_out", "w", "b"):  # biases to d / norm scales
        return (None,)
    # fallback: replicate
    return tuple(None for _ in shape)


def param_specs(cfg: ModelConfig, mesh: Mesh, params_shape,
                mode: str = "train") -> Any:
    """Pytree of PartitionSpecs matching ``params_shape`` (a pytree of
    ShapeDtypeStructs or arrays). mode: "train" (FSDP x TP) | "serve"
    (full 2D TP, weights resident — see _base_rule)."""
    fsdp = fsdp_axes(mesh)

    def rule(path, leaf):
        keys = [e.key for e in path if isinstance(e, jax.tree_util.DictKey)]
        name = keys[-1] if keys else None
        shape = leaf.shape
        is_moe = "moe" in keys
        if is_moe and name in ("w_in", "w_gate", "w_out") and mode == "train":
            # (..., E, d, f) or (..., E, f, d): expert dim at -3.
            base = _base_rule(cfg, mesh, name, shape)  # covers last 2 dims
            e_dim = shape[-3]
            if _divisible(e_dim, mesh, fsdp):
                # FSDP the expert dim; drop fsdp from the trailing dims
                base = tuple(None if b == fsdp else b for b in base)
                lead = [None] * (len(shape) - 3) + [fsdp]
            else:
                lead = [None] * (len(shape) - 2)
            return P(*lead, *base)
        base = _base_rule(cfg, mesh, name, shape, mode)
        lead = [None] * (len(shape) - len(base))
        return P(*lead, *base)

    return jax.tree_util.tree_map_with_path(rule, params_shape)


# --------------------------------------------------------------------------- #
# batch / cache specs
# --------------------------------------------------------------------------- #
def batch_axes(mesh: Mesh, global_batch: int):
    """Largest prefix of the data axes that divides the batch."""
    axes = []
    size = 1
    for a in fsdp_axes(mesh):
        if global_batch % (size * mesh.shape[a]) == 0:
            axes.append(a)
            size *= mesh.shape[a]
    return tuple(axes) or None


def train_batch_specs(cfg: ModelConfig, mesh: Mesh, global_batch: int) -> Dict[str, P]:
    dp = batch_axes(mesh, global_batch)
    specs = {"tokens": P(dp, None), "labels": P(dp, None)}
    if cfg.is_encoder_decoder:
        specs["frames"] = P(dp, None, None)
    if cfg.num_prefix_embeds > 1:
        specs["prefix_embeds"] = P(dp, None, None)
    return specs


def cache_specs(cfg: ModelConfig, mesh: Mesh, batch: int, caches_shape) -> Any:
    """Decode caches: batch over data axes; KV sequence over `model`.
    B=1 (long_500k): context over ALL axes instead."""
    dp = batch_axes(mesh, batch)
    ctx_axes = ("model",) if dp else tuple(mesh.axis_names)

    def rule(path, leaf):
        name = None
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.DictKey):
                name = entry.key
                break
        shape = leaf.shape
        if name in ("k", "v", "mk", "mv", "k_scale", "v_scale"):
            # (N, B, W, KVH*hd), (N|L, B, W, KVH, hd) or scales (N, B, W, KVH)
            seq = shape[2]
            seq_ax = ctx_axes if seq % _size(mesh, ctx_axes) == 0 else None
            return P(None, dp, seq_ax, *[None] * (len(shape) - 3))
        if name == "ssm":  # (N, B, nh, hd, ds)
            nh_ax = "model" if shape[-3] % mesh.shape["model"] == 0 else None
            return P(*[None] * (len(shape) - 4), dp, nh_ax, None, None)
        if name in ("conv_x", "conv_bc"):  # (N, B, kw-1, C)
            c_ax = "model" if shape[-1] % mesh.shape["model"] == 0 else None
            return P(*[None] * (len(shape) - 3), dp, None, c_ax)
        return P(*[None] * len(shape))

    return jax.tree_util.tree_map_with_path(rule, caches_shape)


def _size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    s = 1
    for a in axes if isinstance(axes, tuple) else (axes,):
        s *= mesh.shape[a]
    return s


def opt_state_specs(pspecs) -> Any:
    """AdamW state mirrors params: (step P(), m/v like params)."""
    from repro.optim.adamw import AdamWState

    return AdamWState(step=P(), m=pspecs, v=pspecs)


def named(mesh: Mesh, tree) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree,
        is_leaf=lambda x: isinstance(x, P),
    )
