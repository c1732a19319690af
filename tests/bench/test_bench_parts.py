"""The benchmark's parts without a chip: the traffic generator, the FLOP
count, the peaks table, finding parts by file name, ``BENCHMARK.json``
against its files, and the command's refusal to run without a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import catalog, flops

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
COT = catalog.load_traffic("cot-p256")


def _draw(seed, iters=16, prompts=1, mix=COT):
    gen = catalog.traffic_generator(mix)
    it = gen.batches(mix, seed=seed, prompts_per_iter=prompts,
                     vocab_size=19_008)
    return [next(it) for _ in range(iters)]


def test_traffic_is_deterministic_per_seed():
    a, b = _draw(2**31 + 17), _draw(2**31 + 17)
    for x, y in zip(a, b):
        for f in ("prompts", "true_len", "answers", "budgets"):
            np.testing.assert_array_equal(getattr(x, f), getattr(y, f))


def test_seeds_change_order_and_ids_not_sizes():
    a, b = _draw(5, prompts=2), _draw(6, prompts=2)
    assert any(not np.array_equal(x.prompts, y.prompts) for x, y in zip(a, b))
    assert any(not np.array_equal(x.budgets, y.budgets) for x, y in zip(a, b))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.sort(x.true_len), np.sort(y.true_len))
        np.testing.assert_array_equal(np.sort(x.budgets), np.sort(y.budgets))


def test_traffic_draws_the_stated_mix():
    batches = _draw(3, iters=64)
    budgets = np.concatenate([b.budgets for b in batches])
    assert len(budgets) == 64 * COT["group_size"]
    shares = [np.mean((budgets >= lo) & (budgets <= hi) & (
        budgets < 1024 if hi < 1024 else True)) for _, lo, hi in
        COT["budgets"]]
    np.testing.assert_allclose(shares, [0.6, 0.3, 0.1], atol=0.02)
    assert budgets.max() == COT["max_new"]
    lens = np.concatenate([b.true_len for b in batches])
    lo, hi = COT["prompt_len"]["lo"], COT["prompt_len"]["hi"]
    assert lens.min() >= lo and lens.max() <= hi
    # log-uniform: the median sits at the geometric mean of the ends
    assert abs(np.median(np.log(lens)) - np.log(np.sqrt(lo * hi))) < 0.1


def test_prompts_are_right_padded_ids_of_the_vocabulary():
    for b in _draw(9, iters=4, prompts=3):
        for row, n in zip(b.prompts, b.true_len):
            assert np.all(row[:n] >= 3) and np.all(row[:n] < 19_008)
            assert np.all(row[n:] == 0)
            assert row.shape == (COT["prompt_width"],)


QWEN = catalog.load_config("qwen2.5-7b-l2-v8")["layout"]
MAMBA = catalog.load_config("mamba2-2.7b-l12")["layout"]


def test_matmul_params_by_hand():
    # qwen: q and o 3584 x (28 x 128), k and v 3584 x 512, SwiGLU 3 x 3584 x
    # 18944, two layers, untied head 3584 x 19008
    layer = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944
    assert flops.matmul_params(QWEN) == 2 * layer + 3584 * 19008
    assert flops.matmul_params(QWEN) == 534_216_704
    # mamba2: in-projections to z and x (5120 each), B and C (128), dt (80
    # heads); out 5120 x 2560; twelve layers; tied head 2560 x 50280
    layer = 2560 * (2 * 5120 + 2 * 128 + 80) + 5120 * 2560
    assert flops.matmul_params(MAMBA) == 12 * layer + 2560 * 50280
    assert flops.matmul_params(MAMBA) == 610_897_920


def test_iteration_flops_by_hand():
    # one row: prompt 3, response 2. Generation runs 4 token forwards reading
    # 1+2+3+4 tokens; the reference forward and the step 5 tokens reading
    # 1+...+5; attention is 4 x 28 x 128 per token read per layer
    n, att = 534_216_704, 4 * 28 * 128 * 2
    fwd5 = 5 * 2 * n + 15 * att
    want = (4 * 2 * n + 10 * att) + fwd5 + 3 * fwd5
    assert flops.iteration_flops(QWEN, [3], [2]) == pytest.approx(want)
    # mamba2: no context term, a fixed SSD term per token and layer
    ssd = 4 * 5120 * 128 + 2 * 4 * (5120 + 2 * 128)
    per = 2 * 610_897_920 + 12 * ssd
    assert flops.iteration_flops(MAMBA, [3], [2]) == pytest.approx(
        4 * per + 5 * per + 15 * per)


def test_peaks_by_device_kind_and_unknown_kind_is_an_error():
    assert catalog.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        catalog.peaks("TPU v9 imaginary")


def test_parts_are_found_by_file_name_alone(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(CHECKOUT / "bench", root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    # a new configuration, traffic mix (same generator), cell and metric
    cfg = json.loads((root / "configs" / "qwen2.5-7b-l2-v8.json").read_text())
    cfg["num_hidden_layers"] = 4
    (root / "configs" / "new-model.json").write_text(json.dumps(cfg))
    mix = dict(COT, max_new=64)
    (root / "traffic" / "short.json").write_text(json.dumps(mix))
    cell = json.loads((root / "workloads" / "qwen2.5-7b-grpo-cot.json")
                      .read_text())
    cell.update(config="new-model", traffic="short")
    (root / "workloads" / "new-cell.json").write_text(json.dumps(cell))
    (root / "metrics" / "new_metric.x.py").write_text(
        "LAYER = 'device'\nUNIT = 'count'\nSOURCE = 'program_counter'\n"
        "MOVES = 'tokens_per_s_per_chip'\nBETTER = 'lower'\n"
        "def read(ctx):\n    return 7\n")
    found = catalog.load_cell("new-cell", root)
    assert found["config_spec"]["num_hidden_layers"] == 4
    assert found["traffic_spec"]["max_new"] == 64
    assert catalog.traffic_generator(found["traffic_spec"], root).batches
    readers = catalog.metric_readers(root)
    assert readers["new_metric.x"].read(None) == 7
    assert set(catalog.metric_readers()) < set(readers)
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} changed"


def test_benchmark_json_matches_its_files():
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    readers = catalog.metric_readers()
    for w in spec["workloads"]:
        cell = catalog.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert cell["why"] == w["why"]
    for c in spec["configs"]:
        assert (CHECKOUT / c["file"]).is_file()
        assert set(c["reduced"]) == set(
            catalog.load_config(c["name"])["reduced"])
    for m in spec["per_layer"]:
        mod = readers[m["name"]]
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES, mod.BETTER) == (
            m["layer"], m["unit"], m["source"], m["moves"], m["better"])
        assert "workloads" not in m  # every reader reads in every cell
    assert set(readers) == {m["name"] for m in spec["per_layer"]}


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2.5-7b-grpo-cot",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
