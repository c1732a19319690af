"""jit'd dispatch wrappers over the Pallas kernels.

Mode resolution (``REPRO_KERNEL_MODE`` env var or :func:`set_mode`):
  auto      -> Pallas on TPU backends, pure-jnp ref elsewhere (CPU dry-run
               lowers the ref path; Mosaic has no CPU target)
  pallas    -> force compiled Pallas
  interpret -> Pallas with interpret=True (kernel-correctness tests on CPU)
  ref       -> force pure-jnp oracles

Backward passes: ``flash_attention``, ``rmsnorm`` and ``ssd`` are
differentiable in every mode. In ``pallas`` and ``interpret`` mode the
forward is the Pallas kernel and the backward is ``jax.vjp`` of the matching
``kernels/ref.py`` oracle, recomputed from the saved inputs — it runs as
plain XLA (no Pallas backward kernel exists yet), under a
``jax.named_scope`` named ``<op>_bwd`` so profiles and HLO show it.

Meshes: GSPMD cannot partition a Mosaic kernel, so under an ambient mesh of
several devices every Pallas call runs through ``jax.shard_map``, one batch
shard per data-parallel group (:func:`_batch_parallel`).
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as _ref
from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import sampling as _sm
from repro.kernels import ssd as _ssd
from repro.kernels import rmsnorm as _rn
from repro.utils.jax_compat import ambient_mesh, shard_map

_MODE: Optional[str] = None


def set_mode(mode: Optional[str]) -> None:
    """Override kernel dispatch: auto | pallas | interpret | ref | None."""
    global _MODE
    _MODE = mode


def current_mode() -> str:
    mode = _MODE or os.environ.get("REPRO_KERNEL_MODE", "auto")
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return mode


def _batch_parallel(kernel, batch_axes):
    """``kernel`` run per data shard of the ambient mesh.

    ``batch_axes`` gives, per positional argument, the axis that holds the
    batch (split over every mesh axis but ``model``), or None for an
    argument that is replicated (weights, the page pool, a layer index).
    Every output carries the batch on axis 0. A batch the data axes do not
    divide runs whole on every device. Without a mesh of several devices the
    kernel is returned as it is."""
    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1:
        return kernel
    axes = tuple(a for a in mesh.axis_names if a != "model")

    def run(*args):
        i = next(i for i, a in enumerate(batch_axes) if a is not None)
        batch = args[i].shape[batch_axes[i]]
        split = batch % math.prod(mesh.shape[a] for a in axes) == 0

        def spec(axis):
            if axis is None or not split:
                return P()
            return P(*[None] * axis, axes)

        return shard_map(
            kernel, mesh=mesh, out_specs=spec(0), check_vma=False,
            in_specs=tuple(spec(a) for a in batch_axes))(*args)

    return run


def _with_ref_vjp(name, kernel, reference):
    """``kernel`` as the forward, ``jax.vjp(reference)`` recomputed from the
    saved inputs as the backward. Both take the same positional arrays and
    agree in value; static options are bound by the caller."""

    @jax.custom_vjp
    def op(*args):
        return kernel(*args)

    def fwd(*args):
        return kernel(*args), args

    def bwd(args, g):
        with jax.named_scope(f"{name}_bwd"):
            _, vjp = jax.vjp(reference, *args)
            return vjp(g)

    op.defvjp(fwd, bwd)
    return op


def flash_attention(
    q, k, v, *, causal=True, window=None, scale=None, q_offset=0
):
    """Differentiable in every mode; the Pallas modes take their backward
    from the reference (see the module docstring)."""
    mode = current_mode()
    opts = dict(causal=causal, window=window, scale=scale, q_offset=q_offset)
    reference = functools.partial(_ref.flash_attention, **opts)
    if mode == "ref":
        return reference(q, k, v)
    kernel = _batch_parallel(functools.partial(
        _fa.flash_attention, interpret=(mode == "interpret"), **opts),
        (0, 0, 0))
    return _with_ref_vjp("flash_attention", kernel, reference)(q, k, v)


def decode_attention(
    q, k, v, cache_len, *, layer=None, scale=None, window=None, pos_offset=0
) -> Tuple[jax.Array, jax.Array]:
    """Returns (o, lse) in every mode (shard-combinable). ``k``/``v`` are
    one layer's (B, S, KVH, D) cache or, with ``layer``, a decode loop's
    stacked lane-folded arena (N, B, S, KVH*D), read at layer ``layer``."""
    mode = current_mode()
    if mode == "ref":
        if layer is not None:
            D = q.shape[-1]
            k, v = (a[layer].reshape(*a.shape[1:-1], -1, D) for a in (k, v))
        return _ref.decode_attention(
            q,
            k,
            v,
            cache_len,
            scale=scale,
            window=window,
            pos_offset=pos_offset,
            return_lse=True,
        )
    kernel = functools.partial(
        _da.decode_attention, scale=scale, window=window,
        pos_offset=pos_offset, interpret=(mode == "interpret"))
    if layer is None:
        return _batch_parallel(kernel, (0,) * 4)(q, k, v, cache_len)
    return _batch_parallel(
        lambda q, k, v, n, i: kernel(q, k, v, n, layer=i),
        (0, 1, 1, 0, None))(q, k, v, cache_len, jnp.asarray(layer, jnp.int32))


def decode_attention_quant(
    q, k, v, k_scale, v_scale, cache_len, *, scale=None, window=None,
    pos_offset=0
) -> Tuple[jax.Array, jax.Array]:
    """int8-cache flash decode; returns (o, lse) in every mode. The Pallas
    path fuses dequantization into the tile loop; the ref path dequantizes
    up front (bitwise-identical to the pre-fusion ``_decode_quant``)."""
    mode = current_mode()
    if mode == "ref":
        return _ref.decode_attention_quant(
            q, k, v, k_scale, v_scale, cache_len,
            scale=scale, window=window, pos_offset=pos_offset,
            return_lse=True,
        )
    kernel = functools.partial(
        _da.decode_attention_quant, scale=scale, window=window,
        pos_offset=pos_offset, interpret=(mode == "interpret"))
    return _batch_parallel(kernel, (0,) * 6)(
        q, k, v, k_scale, v_scale, cache_len)


def paged_decode_attention(
    q, pool_k, pool_v, tables, kv_len, *, scale=None
) -> Tuple[jax.Array, jax.Array]:
    """Flash decode straight out of the paged KV pool — the kernel gathers
    each sequence's pages through its block-table row, so the serving burst
    never stages pages into contiguous per-slot KV rows. Returns (o, lse)
    in every mode."""
    mode = current_mode()
    if mode == "ref":
        return _ref.paged_decode_attention(
            q, pool_k, pool_v, tables, kv_len, scale=scale, return_lse=True
        )
    kernel = functools.partial(
        _da.paged_decode_attention, scale=scale,
        interpret=(mode == "interpret"))
    return _batch_parallel(kernel, (0, None, None, 0, 0))(
        q, pool_k, pool_v, tables, kv_len)


def _row_seeds(keys: jax.Array) -> jax.Array:
    """Per-row int32 seeds for the fused sampler's counter-based hash RNG,
    derived from a batch of PRNG keys."""
    bits = jax.vmap(lambda k: jax.random.bits(k, (), jnp.uint32))(keys)
    return jax.lax.bitcast_convert_type(bits, jnp.int32)


def fused_sample(
    h, w_head, key, temperature, *, vocab_size=None, top_p: float = 1.0
) -> Tuple[jax.Array, jax.Array]:
    """Fused LM-head + sampler for one decode step: (hidden, head weights)
    -> (sampled token, behaviour logprob of that token under the untempered
    masked distribution).

    The ref path IS the pre-fusion op sequence (matmul, vocab mask,
    ``jax.random.categorical``, ``log_softmax`` gather) — bitwise-identical
    to what ``rl/rollout.generate`` historically computed. The Pallas path
    streams head-weight tiles and samples via hash-Gumbel-max in-kernel:
    same distribution, different random stream. ``temperature`` and
    ``top_p`` are static floats; ``top_p < 1`` routes to the ref path (the
    kernel's online sweep cannot see the sorted CDF)."""
    mode = current_mode()
    if mode == "ref" or top_p < 1.0:
        return _ref.fused_sample(
            h, w_head, key, temperature, vocab_size=vocab_size, top_p=top_p
        )
    B = h.shape[0]
    seeds = _row_seeds(jax.random.split(key, B))
    inv_t = jnp.full(
        (B,), 0.0 if temperature == 0.0 else 1.0 / temperature, jnp.float32
    )
    return _sampler(vocab_size, mode)(h, w_head, seeds, inv_t)


def fused_sample_rows(h, w_head, keys, temps, *, vocab_size=None) -> jax.Array:
    """Per-row-temperature variant for the serving engine: ``temps`` is a
    traced (B,) array, rows with ``temps <= 0`` take the argmax. Returns the
    sampled tokens only (serving keeps no behaviour logprobs)."""
    mode = current_mode()
    if mode == "ref":
        return _ref.fused_sample_rows(
            h, w_head, keys, temps, vocab_size=vocab_size
        )
    seeds = _row_seeds(keys)
    inv_t = jnp.where(
        temps <= 0.0, 0.0, 1.0 / jnp.maximum(temps, 1e-6)
    ).astype(jnp.float32)
    tok, _ = _sampler(vocab_size, mode)(h, w_head, seeds, inv_t)
    return tok


def _sampler(vocab_size, mode):
    kernel = functools.partial(_sm.fused_sample, vocab_size=vocab_size,
                               interpret=(mode == "interpret"))
    return _batch_parallel(kernel, (0, None, 0, 0))


def combine_decode_shards(o_parts, lse_parts):
    return _ref.combine_decode_shards(o_parts, lse_parts)


def ssd(x, dt, A, Bm, Cm, D, *, chunk=128, return_state=False):
    """Differentiable in every mode; the Pallas modes take their backward
    from the chunked reference (see the module docstring)."""
    mode = current_mode()
    reference = functools.partial(
        _ref.ssd_chunked, chunk=min(chunk, x.shape[1]), return_state=True)
    if mode == "ref":
        y, h = reference(x, dt, A, Bm, Cm, D)
    else:
        kernel = _batch_parallel(functools.partial(
            _ssd.ssd, chunk=chunk, interpret=(mode == "interpret")),
            (0, 0, None, 0, 0, None))
        y, h = _with_ref_vjp("ssd", kernel, reference)(x, dt, A, Bm, Cm, D)
    if return_state:
        return y, h
    return y


def ssd_decode_step(x, dt, A, Bm, Cm, D, h):
    # single-token step is pure VPU work; the jnp form is already minimal
    return _ref.ssd_decode_step(x, dt, A, Bm, Cm, D, h)


def rmsnorm(x, w, *, eps: float = 1e-6):
    """Differentiable in every mode; the Pallas modes take their backward
    from the reference (see the module docstring)."""
    mode = current_mode()
    reference = functools.partial(_ref.rmsnorm, eps=eps)
    if mode == "ref":
        return reference(x, w)
    kernel = functools.partial(
        _rn.rmsnorm, eps=eps, interpret=(mode == "interpret"))
    if x.ndim > 1:
        kernel = _batch_parallel(kernel, (0, None))
    return _with_ref_vjp("rmsnorm", kernel, reference)(x, w)
