"""CPU rehearsal of ``chip_smoke.py``: it refuses to run without a TPU, and
its kernel, RL and serving phases run end to end at a tiny size with the
Pallas kernels in interpret mode (which finds wrong paths, arguments and
control flow without the chip)."""
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke
from repro.configs import get_config, reduced
from repro.kernels import ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_KERNEL_MODE", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert "JAX backend is a TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_refuses_outside_the_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _run(tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.fixture
def interpret_mode():
    ops.set_mode("interpret")
    try:
        yield
    finally:
        ops.set_mode(None)


def test_phases_run_tiny_in_interpret_mode(interpret_mode):
    chip_smoke.check_kernels(batch=2, seq=64, heads=4, kv_heads=2,
                             head_dim=16, d_model=64, vocab=250, page=8)
    cfg = reduced(get_config("qwen2.5-7b"), vocab_size=260, num_layers=2)
    pipe, history = chip_smoke.run_rl(
        chip_smoke.smoke_experiment(cfg, prompts=2, group=2, max_new=4))
    # interpret-mode kernels lower to plain HLO: no TPU kernel marker
    chip_smoke.check_rl(pipe, history, kernel_marker=None)
    model, params = chip_smoke.pipe_model(pipe), pipe.ctx.actor_state.params
    chip_smoke.run_serving(model, params, num_requests=3, max_len=64,
                           max_new=4, slots=2)
