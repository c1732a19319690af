"""Small cells for the benchmark's tests: the qwen cell's file, with the
dense or the SSD configuration, and every size cut so that a run fits a
CPU test (the program dispatches its kernels to their jnp references
there)."""
import copy

import pytest

from bench import catalog

TINY = {
    "dense": ("qwen2.5-7b-l2-v8",
              dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                   head_dim=16, d_ff=128, vocab_size=500, pad_heads_to=1),
              dict(num_layers=2, d_model=64, num_heads=4, padded_heads=4,
                   num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=500,
                   padded_vocab=512)),
    "ssm": ("mamba2-2.7b-l12",
            dict(num_layers=2, d_model=64, ssm_state=16, ssm_headdim=16,
                 vocab_size=500),
            dict(num_layers=2, d_model=64, ssm_state=16, ssm_headdim=16,
                 vocab_size=500, padded_vocab=512)),
}


# Limits for the small cells, set as the cells' own are: from readings of
# sound runs (seeds 5, 6, 7) and of the float8 control at this size on the
# CPU. Sound: logprob gaps <= 0.05, loss <= 1e-4, gradient <= 0.009, update
# <= 0.0015 (dense) and <= 0.031 (ssm). Control: logprob gaps >= 0.17,
# loss >= 2e-4, gradient >= 0.039. The half batch reads a gradient gap
# >= 0.26, a state left unchanged 1.
TINY_LIMITS = {"gen_logprob_gap": 0.12, "ref_logprob_gap": 0.12,
               "loss_rel_gap": 1.5e-4, "grad_leaf_gap": 0.02}
TINY_UPDATE_LIMIT = {"dense": {"update_leaf_gap": 0.005},
                     "ssm": {"update_leaf_gap": 0.1}}


def tiny_cell(kind: str) -> dict:
    config, overrides, layout = TINY[kind]
    cell = copy.deepcopy(catalog.load_cell("qwen2.5-7b-grpo-cot"))
    cell["config_spec"] = catalog.load_config(config)
    cell["config_spec"]["program"]["overrides"] = overrides
    cell["config_spec"]["layout"].update(layout)
    cell["traffic_spec"].update(
        group_size=4, prompt_len={"lo": 4, "hi": 16}, prompt_width=16,
        max_new=16, budgets=[[0.6, 2, 6], [0.3, 6, 12], [0.1, 16, 16]])
    cell["prompts_per_iter"] = 2
    cell["rl"]["lr"] = 1e-3  # moves the small model's bfloat16 weights
    cell["limits"] = dict(TINY_LIMITS, **TINY_UPDATE_LIMIT[kind])
    return cell


@pytest.fixture
def tiny():
    return tiny_cell
