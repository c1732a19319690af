"""Dry-run machinery tests at reduced scale (subprocess, 8 host devices):
the same build_workload / lower / compile / analyze path as the production
dry-run, on a (2,4) mesh with reduced configs."""
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
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=540, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


@pytest.mark.parametrize("arch", ["deepseek-67b", "mixtral-8x7b", "mamba2-2.7b",
                                  "jamba-v0.1-52b"])
def test_workload_cells_compile_small_mesh(arch):
    out = run_py(f"""
        import jax
        from repro.configs import ARCHS, reduced
        from repro.configs.base import ShapeConfig
        from repro.launch.workloads import build_workload
        mesh = make_compat_mesh((2, 4), ('data', 'model'))
        cfg = reduced(ARCHS[{arch!r}], d_model=64, num_heads=4, num_kv_heads=4,
                      head_dim=16, vocab_size=256)
        with use_mesh(mesh):
            for kind, (S, B) in {{'train': (64, 8), 'prefill': (64, 8),
                                  'decode': (64, 8)}}.items():
                wl = build_workload(cfg, ShapeConfig('t', S, B, kind), mesh)
                compiled = wl.fn.lower(*wl.args).compile()
                mem = compiled.memory_analysis()
                assert mem.peak_memory_in_bytes > 0
        print('OK')
    """)
    assert "OK" in out


def test_collective_parser_sees_spmd_collectives():
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.utils.hlo import collective_bytes
        mesh = make_compat_mesh((8,), ('data',))

        def f(x):  # force an all-reduce: contraction over a sharded dim
            return jnp.sum(x, axis=0)
        fn = jax.jit(f, in_shardings=NamedSharding(mesh, P('data', None)),
                     out_shardings=NamedSharding(mesh, P(None)))
        compiled = fn.lower(jax.ShapeDtypeStruct((64, 32), jnp.float32)).compile()
        coll = collective_bytes(compiled.as_text())
        assert coll['total_count'] >= 1, compiled.as_text()[:2000]
        assert coll['total_bytes'] > 0
        print('OK', coll['per_kind_count'])
    """)
    assert "OK" in out


def test_roofline_extrapolation_consistency():
    """m(L) extrapolated from (P, 2P) must match a direct 4P-depth compile
    within 10% — the linearity assumption behind the roofline table."""
    out = run_py("""
        import dataclasses, jax
        from repro.configs import ARCHS, reduced
        from repro.configs.base import ShapeConfig
        from repro.launch.workloads import build_workload
        from repro.utils.hlo import collective_bytes, cost_summary
        mesh = make_compat_mesh((2, 4), ('data', 'model'))
        base = reduced(ARCHS['deepseek-67b'], d_model=64, num_heads=4,
                       num_kv_heads=4, head_dim=16, vocab_size=256)
        shape = ShapeConfig('t', 64, 8, 'train')

        def metrics(L):
            cfg = dataclasses.replace(base, num_layers=L)
            with use_mesh(mesh):
                wl = build_workload(cfg, shape, mesh, unroll=True)
                c = wl.fn.lower(*wl.args).compile()
            cost = cost_summary(c.cost_analysis())
            return cost['flops']
        f1, f2, f4 = metrics(1), metrics(2), metrics(4)
        pred4 = f1 + (f2 - f1) * 3
        rel = abs(pred4 - f4) / f4
        assert rel < 0.10, (f1, f2, f4, pred4, rel)
        print('OK rel=%.3f' % rel)
    """)
    assert "OK" in out
