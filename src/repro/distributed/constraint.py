"""Activation sharding constraints with logical axis names.

Model code annotates activations with *logical* entries ('dp' = all non-model
mesh axes, 'tp' = the `model` axis); the ambient mesh (set via
``jax.sharding.set_mesh`` by the launcher / dry-run) resolves them. With no
ambient mesh (unit tests, CPU examples) every call is a no-op, so model code
stays mesh-agnostic.

These constraints are what steer GSPMD to the FSDP execution we want: weights
are ALL-GATHERED at use (ZeRO-3) instead of activations being resharded onto
the weights' FSDP axis — without them, GSPMD happily un-shards the batch to
contract over an FSDP-sharded d_model dim (observed: a 16 GB fp32 all-reduce
in the CE loss).
"""
from __future__ import annotations

import os

import jax
from jax.sharding import PartitionSpec as P


def sequence_parallel() -> bool:
    """Megatron-SP toggle: shard the residual stream's seq dim over `model`
    between blocks, turning TP all-reduces into reduce-scatter/all-gather
    pairs (half the wire bytes) and sharding norm work.

    Default OFF: measured on the production mesh, GSPMD turned this
    constraint into full-activation resharding storms (15.6 TB/step vs
    976 GB/step collectives on deepseek-67b:train_4k — §Perf C-it1,
    REFUTED). Set REPRO_SP=1 to reproduce that arm."""
    return os.environ.get("REPRO_SP", "0") == "1"


def residual_entries():
    return ("dp", "tp", None) if sequence_parallel() else ("dp", None, None)


def tp_shards(dim: int) -> int:
    """How many shards ``constrain``'s 'tp' entry splits a dimension of
    size ``dim`` into under the ambient mesh: the `model` axis's size where
    it divides ``dim``, else 1 (also with no mesh, or inside shard_map)."""
    from repro.utils.jax_compat import ambient_mesh

    mesh = ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    n = dict(mesh.shape)["model"]
    return n if dim % n == 0 else 1


def constrain(x: jax.Array, *entries) -> jax.Array:
    """entries: 'dp' | 'tp' | None per dim (trailing dims may be omitted).

    No-op without an ambient mesh, and inside shard_map manual regions
    (with_sharding_constraint only accepts Auto axes — the manual caller has
    already fixed the layout)."""
    from repro.utils.jax_compat import ambient_mesh

    mesh = ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return x
    dp = tuple(a for a in mesh.axis_names if a != "model")
    sizes = dict(mesh.shape)
    spec = []
    for dim, e in zip(x.shape, entries):
        if e == "dp":
            n = 1
            axes = []
            for a in dp:
                if dim % (n * sizes[a]) == 0:
                    axes.append(a)
                    n *= sizes[a]
            spec.append(tuple(axes) if axes else None)
        elif e == "tp":
            spec.append("model" if dim % sizes["model"] == 0 else None)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
