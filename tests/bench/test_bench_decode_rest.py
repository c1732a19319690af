"""``decode_share.rest`` (``bench/metrics/decode_share.rest.py``): the decode
burst's leaf-op time outside the model's ``mixer``, ``mlp`` and ``head``
scopes, on the synthetic window of ``test_bench_program_trace.py`` and on a
window recorded on a TPU v5e."""
import gzip
import json

import pytest

from bench import harness, program_trace
from bench import trace as tr

import test_bench_program_trace as base

REST = "decode_share.rest"


@pytest.mark.parametrize("scoped", [False, True])
def test_decode_rest_is_the_burst_outside_the_layer_scopes(scoped):
    """The burst's leaf ops under none of the three layer scopes (the copy,
    50 of 1000 ns); the while loop that holds them and the step's ops are
    not counted. Absent without name stacks."""
    red = program_trace.reduce(base._synthetic(scoped), [0])
    got = harness.per_layer_metrics(base._context(red))
    if not scoped:
        assert REST not in got
        return
    assert got[REST]["value"] == pytest.approx(0.05)
    assert sum(got[m]["value"] for m in base.NEW + (REST,)) == \
        pytest.approx(0.3)  # every leaf of the burst, once


def test_decode_rest_on_a_window_recorded_with_obs_on():
    """The TPU v5e window of ``qwen2.5-7b-grpo-cot`` (12 ms of decode burst
    among it): the rest and the three layer shares add up to the burst's
    leaf time; the trace without name stacks gives no reading."""
    with gzip.open(base.FIXTURES / "trace_qwen_v5e_spans.json.gz", "rt") as f:
        red = program_trace.reduce(json.load(f), [0])
    got = {k: v["value"] for k, v in
           harness.per_layer_metrics(base._context(red)).items()}
    assert got[REST] == pytest.approx(0.045236980565216973, rel=1e-9)
    leaves = red.op_seconds(lambda op, mod: mod == program_trace.BURST
                            and not tr.is_container(op)) / red.window_s
    assert got[REST] + sum(got[m] for m in base.NEW) == \
        pytest.approx(leaves, rel=1e-9)
    with gzip.open(base.FIXTURES / "trace_qwen_v5e.json.gz", "rt") as f:
        bare = tr.reduce(json.load(f), [0])  # no name stacks
    assert REST not in harness.per_layer_metrics(base._context(bare))
