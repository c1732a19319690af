"""Multi-device behaviour tests. Each test runs a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest process
keeps seeing 1 device (per the dry-run isolation rule). The children are
CPU-forced on purpose: these are CPU simulations of a mesh, not chip runs
(a child cannot reach a chip its parent holds)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str) -> str:
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n"
        "import sys\n"
        f"sys.path.insert(0, {os.path.join(REPO, 'src')!r})\n"
        "from repro.utils.jax_compat import make_compat_mesh, use_mesh, shard_map\n"
        + textwrap.dedent(body)
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


def test_databuffer_all_to_all_dp_resize():
    """Paper Fig. 7-8: gen stage DP=2 -> train stage DP=8. Values preserved,
    no controller traffic, redistribution counted."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import DistributedDatabuffer
        mesh = make_compat_mesh((2, 4), ('data', 'model'))
        buf = DistributedDatabuffer(mesh)
        x = jnp.arange(16 * 4.0).reshape(16, 4)
        buf.put('x', x, P('data', None))          # DP=2 (model-replicated)
        y = buf.get('x', P(('data', 'model'), None))  # DP=8
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        assert buf.stats.redistributions == 1
        assert buf.stats.bytes_through_controller == 0
        assert len(y.sharding.device_set) == 8
        # fast path back
        z = buf.get('x', P('data'))
        assert buf.stats.fast_path_hits == 1
        print('OK')
    """)
    assert "OK" in out


def test_load_balance_repack_preserves_sharding():
    """The post-GENERATE length-aware repack must keep arrays under the
    producer's data sharding — a bare jnp.take would replicate the full
    global batch onto every device (invisible on the 1x1 CI mesh)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import DataCoordinatorConfig
        from repro.core import DistributedDatabuffer
        from repro.core.worker import DAGWorker
        mesh = make_compat_mesh((2, 4), ('data', 'model'))
        buf = DistributedDatabuffer(mesh)
        B = 8
        lengths = np.array([13, 9, 1, 1, 5, 3, 1, 1])
        mask = (np.arange(16)[None, :] < lengths[:, None]).astype(np.int32)
        buf.put('response_mask', jnp.asarray(mask), P('data', None))
        buf.put('tokens', jnp.arange(B * 16).reshape(B, 16), P('data', None))
        w = DAGWorker.__new__(DAGWorker)
        w.buffer = buf
        w.coordinator = DataCoordinatorConfig(load_balance=True, num_buckets=4)
        class C: pass
        class RL: algorithm = 'ppo'; group_size = 1
        w.ctx = C(); w.ctx.mesh = mesh; w.ctx.rl = RL()
        m = w._balance_rollouts()
        assert m['balance/repacked'] == 1.0, m
        assert m['balance/token_ratio_after'] < m['balance/token_ratio_before'], m
        for k in ('tokens', 'response_mask'):
            spec = buf.get(k).sharding.spec
            assert tuple(spec) and tuple(spec)[0] == 'data', (k, spec)
        print('OK')
    """)
    assert "OK" in out


def test_compressed_psum_close_to_exact():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum, ef_update
        mesh = make_compat_mesh((8,), ('data',))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 32))

        def body(xs):
            exact = jax.lax.psum(xs[0], 'data')
            approx = compressed_psum(xs[0], 'data')
            return exact, approx
        exact, approx = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P('data', None, None),),
            out_specs=(P(), P()), check_vma=False))((x,))
        rel = np.abs(np.asarray(exact) - np.asarray(approx)).max() / np.abs(np.asarray(exact)).max()
        assert rel < 0.02, rel
        # error feedback drives bias down over repeats
        err = jnp.zeros((64, 32))
        g = x[0]
        total = jnp.zeros((64, 32))
        for _ in range(8):
            dec, err = ef_update(g, err)
            total = total + dec
        drift = np.abs(np.asarray(total/8) - np.asarray(g)).max()
        assert drift < 0.05, drift
        print('OK')
    """)
    assert "OK" in out


def test_checkpoint_elastic_restore(tmp_path):
    """Save on a (4,2) mesh, restore onto (2,2,2) multi-pod-style mesh AND a
    single device — bitwise identical params."""
    out = run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ft import checkpoint
        mesh = make_compat_mesh((4, 2), ('data', 'model'))
        tree = {{
            'w': jax.device_put(jnp.arange(64.0).reshape(8, 8),
                                NamedSharding(mesh, P('data', 'model'))),
            'b': jax.device_put(jnp.arange(8.0), NamedSharding(mesh, P('model'))),
            'step_scale': jnp.float32(3.5),
        }}
        checkpoint.save({str(tmp_path)!r}, tree, step=17)
        # elastic restore onto a different topology
        mesh2 = make_compat_mesh((2, 2, 2), ('pod', 'data', 'model'))
        specs = {{'w': P(('pod','data'), 'model'), 'b': P(None), 'step_scale': P()}}
        restored, step = checkpoint.restore({str(tmp_path)!r}, tree, mesh=mesh2, specs=specs)
        assert step == 17
        np.testing.assert_array_equal(np.asarray(restored['w']), np.asarray(tree['w']))
        np.testing.assert_array_equal(np.asarray(restored['b']), np.asarray(tree['b']))
        assert float(restored['step_scale']) == 3.5
        # host-only restore (no mesh)
        r2, _ = checkpoint.restore({str(tmp_path)!r}, tree)
        np.testing.assert_array_equal(np.asarray(r2['w']), np.asarray(tree['w']))
        print('OK')
    """)
    assert "OK" in out


def test_seq_sharded_decode_attention_matches_ref():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.collectives import seq_sharded_decode_attention
        from repro.kernels import ref
        mesh = make_compat_mesh((1, 8), ('data', 'model'))
        B, S, H, KVH, D = 2, 64, 4, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, H, D))
        k = jax.random.normal(ks[1], (B, S, KVH, D))
        v = jax.random.normal(ks[2], (B, S, KVH, D))
        cl = jnp.array([40, 64], jnp.int32)
        want = ref.decode_attention(q, k, v, cl)
        got = seq_sharded_decode_attention(mesh, q, k, v, cl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
        print('OK')
    """)
    assert "OK" in out


def test_grpo_pipeline_runs_on_multi_device_mesh():
    """End-to-end DistFlow iteration on a 2x4 mesh: per-stage DP sizes differ
    (model stages dp=2, compute stages dp=8) -> databuffer redistributes."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import ARCHS, reduced
        from repro.core import build_pipeline
        from repro.rl import RLConfig
        mesh = make_compat_mesh((2, 4), ('data', 'model'))
        cfg = reduced(ARCHS['qwen2.5-7b'], vocab_size=260, num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=4, head_dim=16)
        rl = RLConfig(algorithm='grpo', group_size=4, max_new_tokens=8, lr=1e-4)
        with use_mesh(mesh):
            pipe = build_pipeline(cfg, rl, mesh=mesh, prompts_per_iter=4)
            hist = pipe.run(2)
        assert all(abs(h['actor/ratio_mean'] - 1.0) < 0.1 for h in hist)
        assert pipe.buffer.stats.redistributions > 0   # dp-resize exercised
        assert pipe.buffer.stats.bytes_through_controller == 0
        print('OK', pipe.buffer.stats)
    """)
    assert "OK" in out


def test_model_sharded_arena_keeps_the_masked_write():
    """Where the arena's W axis is sharded over a `model` axis of size > 1,
    the decode step writes the new position through an elementwise select
    (a scatter at a traced position made GSPMD all-gather the cache); on a
    batch-sharded (4, 1) mesh it writes one row per sequence in place, and
    no arena-shaped all-gather appears. Both match the step on one device
    (float32 weights, so that the `model` axis's partial sums differ from
    it by rounding alone)."""
    out = run_py("""
        import re
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import ARCHS, reduced
        from repro.models import lm
        cfg = reduced(ARCHS['qwen2.5-7b'], vocab_size=260, num_layers=2)
        params = jax.tree.map(lambda a: a.astype(jnp.float32),
                              lm.init(cfg, jax.random.PRNGKey(0)))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 6), 3, 200)
        _, caches, n = lm.prefill(cfg, params, prompt, smax=16)
        n = n - jnp.asarray([0, 1, 3, 5], jnp.int32)
        tok = jnp.asarray([5, 6, 7, 8], jnp.int32)
        step = lambda p, t, c, n: lm.decode_step(cfg, p, t, c, n)
        want, want_caches, _ = jax.jit(step)(params, tok, caches, n)
        arena = caches[0]['k'].shape

        def arena_selects(jaxpr):
            found = 0
            for eqn in jaxpr.eqns:
                found += eqn.primitive.name == 'select_n' and any(
                    tuple(v.aval.shape) in (arena, arena[1:])
                    for v in eqn.outvars)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    found += arena_selects(sub)
            return found

        assert arena_selects(jax.make_jaxpr(step)(params, tok, caches, n).jaxpr) == 0
        dims = ','.join(map(str, arena[2:]))
        for shape, masked in (((1, 4), True), ((4, 1), False)):
            mesh = make_compat_mesh(shape, ('data', 'model'),
                                    devices=jax.devices()[:4])
            with use_mesh(mesh):
                jaxpr = jax.make_jaxpr(step)(params, tok, caches, n)
                fn = jax.jit(step)
                hlo = fn.lower(params, tok, caches, n).compile().as_text()
                got, got_caches, _ = fn(params, tok, caches, n)
            assert (arena_selects(jaxpr.jaxpr) > 0) == masked, shape
            if not masked:
                assert not re.search(rf'f32\\[[0-9,]*{dims}\\]\\S* all-gather', hlo)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, rtol=1e-5)
            for g, w in zip(got_caches, want_caches):
                for name in ('k', 'v'):
                    np.testing.assert_allclose(np.asarray(g[name]),
                                               np.asarray(w[name]),
                                               atol=1e-5, rtol=1e-5)
        print('OK')
    """)
    assert "OK" in out
