"""Compile every main-path Pallas kernel for a described TPU v5e chip.

Interpret mode accepts tiles that Mosaic refuses (misaligned blocks, too much
fast memory), so these tests lower and compile each kernel — and the
``custom_vjp`` backward of flash attention, rmsnorm and ssd — at the widths
``chip_smoke.py`` runs (qwen2.5-7b: head_dim 128, 32 padded query heads, 4
KV heads, d_model 3584, vocab cut to 19,008 -> 19,200 padded; mamba2-2.7b
for ssd) for one chip of a ``v5e:2x2`` topology, and assert the kernel
is in the HLO. Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scope fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.decode_attention import (
    decode_attention,
    decode_attention_quant,
    paged_decode_attention,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.sampling import fused_sample
from repro.kernels.ssd import ssd

B_DEC, S, H, KVH, D = 8, 2048, 32, 4, 128
D_MODEL, VOCAB_PAD = 3584, 19_200
PAGE = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off (entries
    written for a described chip cannot be read back without one) and the
    default matmul precision the chip runs at: ``test_kernels.py`` raises it
    to "highest" when imported, and Mosaic refuses that for bf16 operands."""
    from jax.experimental.compilation_cache import compilation_cache

    was_cache = jax.config.jax_enable_compilation_cache
    was_precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_cache)
        jax.config.update("jax_default_matmul_precision", was_precision)
        compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    return hlo


bf16, f32, i32, i8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8

KERNELS = {
    "flash_causal_gqa": (
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        [((1, S, H, D), bf16), ((1, S, KVH, D), bf16), ((1, S, KVH, D), bf16)],
    ),
    "decode_dense": (
        lambda q, k, v, n: decode_attention(q, k, v, n),
        [((B_DEC, H, D), bf16), ((B_DEC, S, KVH, D), bf16),
         ((B_DEC, S, KVH, D), bf16), ((B_DEC,), i32)],
    ),
    "decode_ragged_window": (
        lambda q, k, v, n: decode_attention(q, k, v, n, window=1024),
        [((B_DEC, H, D), bf16), ((B_DEC, S, KVH, D), bf16),
         ((B_DEC, S, KVH, D), bf16), ((B_DEC,), i32)],
    ),
    # the decode loop's stacked lane-folded arena (2 layers), read at a
    # scalar-prefetched layer index
    "decode_arena": (
        lambda q, k, v, n, i: decode_attention(q, k, v, n, layer=i),
        [((B_DEC, H, D), bf16), ((2, B_DEC, S, KVH * D), bf16),
         ((2, B_DEC, S, KVH * D), bf16), ((B_DEC,), i32), ((), i32)],
    ),
    "decode_int8": (
        lambda q, k, v, ks, vs, n: decode_attention_quant(q, k, v, ks, vs, n),
        [((B_DEC, H, D), bf16), ((B_DEC, S, KVH, D), i8),
         ((B_DEC, S, KVH, D), i8), ((B_DEC, S, KVH), f32),
         ((B_DEC, S, KVH), f32), ((B_DEC,), i32)],
    ),
    "decode_paged": (
        lambda q, pk, pv, t, n: paged_decode_attention(q, pk, pv, t, n),
        [((B_DEC, H, D), bf16),
         ((B_DEC * S // PAGE, PAGE, KVH, D), bf16),
         ((B_DEC * S // PAGE, PAGE, KVH, D), bf16),
         ((B_DEC, S // PAGE), i32), ((B_DEC,), i32)],
    ),
    "fused_sample": (
        lambda h, w, s, t: fused_sample(h, w, s, t, vocab_size=19_008),
        [((B_DEC, D_MODEL), bf16), ((D_MODEL, VOCAB_PAD), bf16),
         ((B_DEC,), i32), ((B_DEC,), f32)],
    ),
    "rmsnorm": (
        lambda x, w: rmsnorm(x, w),
        [((B_DEC * 256, D_MODEL), bf16), ((D_MODEL,), f32)],
    ),
    # mamba2-2.7b: d_inner 5120 -> 80 heads of 64, one group, state 128
    "ssd": (
        lambda x, dt, a, b, c, d: ssd(x, dt, a, b, c, d, chunk=128),
        [((1, S, 80, 64), bf16), ((1, S, 80), f32), ((80,), f32),
         ((1, S, 1, 128), bf16), ((1, S, 1, 128), bf16), ((80,), f32)],
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    _compile(fn, one_chip, *shapes)


def _flash_loss(q, k, v):
    return jnp.sum(ops.flash_attention(q, k, v, causal=True).astype(f32))


def _rmsnorm_loss(x, w):
    return jnp.sum(ops.rmsnorm(x, w).astype(f32))


def _ssd_loss(x, dt, a, b, c, d):
    y, h = ops.ssd(x, dt, a, b, c, d, chunk=128, return_state=True)
    return jnp.sum(y.astype(f32)) + jnp.sum(h)


BACKWARDS = {
    "flash_attention": (_flash_loss, 3, [((1, 512, H, D), bf16),
                                         ((1, 512, KVH, D), bf16),
                                         ((1, 512, KVH, D), bf16)]),
    "rmsnorm": (_rmsnorm_loss, 2, [((B_DEC * 256, D_MODEL), bf16),
                                   ((D_MODEL,), f32)]),
    "ssd": (_ssd_loss, 6, [((1, 512, 80, 64), bf16), ((1, 512, 80), f32),
                           ((80,), f32), ((1, 512, 1, 128), bf16),
                           ((1, 512, 1, 128), bf16), ((80,), f32)]),
}


@pytest.mark.parametrize("name", sorted(BACKWARDS))
def test_custom_vjp_backward_compiles_for_v5e(one_chip, name):
    """The Pallas forward plus its reference-recompute backward (XLA) in one
    program, as the trainer's ``jax.value_and_grad`` builds it on the chip
    (a bare ``jax.grad`` of a sum never reads the forward's value, so XLA
    would drop the kernel)."""
    loss, nargs, shapes = BACKWARDS[name]
    ops.set_mode("pallas")
    try:
        hlo = _compile(jax.value_and_grad(loss, argnums=tuple(range(nargs))),
                       one_chip, *shapes)
    finally:
        ops.set_mode(None)
    assert f"{name}_bwd" in hlo  # the named scope survives into the HLO


@pytest.fixture(scope="module")
def four_chips(topo, one_chip):
    """A (data=4, model=1) mesh over the described chips; ``one_chip`` keeps
    the persistent cache off for these compiles too."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))


def _flash_step(q, k, v):
    return jax.value_and_grad(_flash_loss, argnums=(0, 1, 2))(q, k, v)


# (fn, [(shape, dtype, axis split over data, or None)])
PARTITIONED = {
    "flash_attention": (_flash_step, [((8, 256, H, D), bf16, 0),
                                      ((8, 256, KVH, D), bf16, 0),
                                      ((8, 256, KVH, D), bf16, 0)]),
    "decode_attention": (
        lambda q, k, v, n: ops.decode_attention(q, k, v, n),
        [((B_DEC, H, D), bf16, 0), ((B_DEC, S, KVH, D), bf16, 0),
         ((B_DEC, S, KVH, D), bf16, 0), ((B_DEC,), i32, 0)]),
    "decode_attention_arena": (
        lambda q, k, v, n, i: ops.decode_attention(q, k, v, n, layer=i),
        [((B_DEC, H, D), bf16, 0), ((2, B_DEC, S, KVH * D), bf16, 1),
         ((2, B_DEC, S, KVH * D), bf16, 1), ((B_DEC,), i32, 0),
         ((), i32, None)]),
    "fused_sample": (
        lambda h, w: ops.fused_sample(h, w, jax.random.PRNGKey(0), 1.0,
                                      vocab_size=19_008),
        [((B_DEC, D_MODEL), bf16, 0), ((D_MODEL, VOCAB_PAD), bf16, None)]),
}


@pytest.mark.parametrize("name", sorted(PARTITIONED))
def test_kernels_partition_over_four_chips(four_chips, name):
    """GSPMD cannot partition a Mosaic kernel: under a data-parallel mesh of
    four chips the ``ops`` dispatch must run each kernel per batch shard
    (shard_map), or this compile raises."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.utils.jax_compat import use_mesh

    fn, shapes = PARTITIONED[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=NamedSharding(
        four_chips, P() if axis is None else P(*[None] * axis, "data")))
        for s, dt, axis in shapes]
    ops.set_mode("pallas")
    try:
        with use_mesh(four_chips):
            hlo = jax.jit(fn).lower(*args).compile().as_text()
    finally:
        ops.set_mode(None)
    assert "tpu_custom_call" in hlo


def test_decode_burst_keeps_the_arena_in_place(one_chip):
    """The continuous engine's decode burst at the benchmark cell's size
    (qwen2.5-7b widths, 2 layers, 8 slots, a 1280-wide arena): the KV arena
    and the output rows are donated and aliased to the outputs, and no op
    copies, relayouts, selects over or restacks the arena or one of its
    layers — each step writes one row per slot and layer with a scatter."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.models import get_model
    from repro.rl.rollout_engine import ContinuousRolloutEngine

    cfg = dataclasses.replace(get_config("qwen2.5-7b"), num_layers=2,
                              vocab_size=19_008)
    model = get_model(cfg)
    slots, max_new, smax = 8, 1024, 1280
    burst = ContinuousRolloutEngine(
        model, max_new=max_new, eos_id=2)._make_burst(slots)

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    caches = shaped(jax.eval_shape(lambda: model.init_caches(slots, smax)))
    out_rows = (arg((slots, max_new), i32), arg((slots, max_new), f32))
    args = (shaped(jax.eval_shape(model.init, jax.random.PRNGKey(0))), caches,
            arg((slots,), i32), arg((slots,), i32), arg((slots,), i32),
            arg((slots,), jnp.bool_), arg((slots,), i32), *out_rows,
            arg((), i32), arg((), i32), arg((max_new - 1, 2), jnp.uint32),
            arg((2,), jnp.uint32), arg((), jnp.bool_))
    ops.set_mode("pallas")
    try:
        compiled = burst.lower(*args).compile()
    finally:
        ops.set_mode(None)
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((caches, out_rows)))
    assert compiled.memory_analysis().alias_size_in_bytes == donated
    arena = caches[0]["k"].shape  # (2, 8, 1280, 512): lane-folded
    assert arena == (2, slots, smax, KVH * D)
    dims = ",".join(map(str, arena))
    touch = re.compile(
        rf"= bf16\[({dims}|{dims.split(',', 1)[1]})\]\S* "
        r"(copy|copy-start|copy-done|reshape|transpose|select|"
        r"dynamic-update-slice|dynamic-slice|concatenate)\(")
    hlo = compiled.as_text()
    assert not [l for l in hlo.splitlines() if touch.search(l)]
    assert len(re.findall(rf"= bf16\[{dims}\]\S* scatter\(", hlo)) == 2
