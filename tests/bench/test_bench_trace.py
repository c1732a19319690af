"""The trace reduction, on a window trimmed from a traced run of
``qwen2.5-7b-grpo-cot`` on a TPU v5e: the end of one iteration's decode
burst, the reference log-prob forward, reward and advantage, and the first
millisecond of the actor step, with the harness's host spans."""
import gzip
import json
import pathlib

import pytest

from bench import catalog, harness
from bench import trace as tr

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_qwen_v5e.json.gz"


@pytest.fixture(scope="module")
def events():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def red(events):
    return tr.reduce(events, [0])


def test_busy_time_is_the_union_of_op_intervals(events, red):
    lo, hi = red.window
    marks = sorted((max(o[1], lo), min(o[1] + o[2], hi))
                   for o in events["devices"]["0"]["ops"])
    busy, end = 0.0, lo
    for s, e in marks:  # a plain sweep over the sorted intervals
        if e > end:
            busy += e - max(s, end)
            end = e
    assert red.busy_s == pytest.approx(busy / 1e9)
    assert red.window_s == pytest.approx(0.107)
    assert red.busy_s == pytest.approx(0.094192905, rel=1e-9)
    gaps = sum(e - s for s, e in red.idle_gaps()) / 1e9
    assert gaps == pytest.approx(red.window_s - red.busy_s)


def test_program_time_is_its_executions_clipped_to_the_window(events, red):
    lo, hi = red.window
    want = sum(min(m[1] + m[2], hi) - max(m[1], lo)
               for m in events["devices"]["0"]["modules"]
               if m[0].startswith("jit_step("))
    assert red.module_seconds(lambda n, ops: n == "jit_step") == \
        pytest.approx(want / 1e9)
    # the log-prob forward is the one lambda program that runs kernels
    lp = red.module_seconds(lambda n, ops: n == "jit__lambda" and any(
        tr.is_kernel(o) for o in ops))
    assert lp == pytest.approx(0.092788747, rel=1e-9)
    other = red.module_seconds(lambda n, ops: n == "jit__lambda" and not any(
        tr.is_kernel(o) for o in ops))
    assert 0 < other < 1e-4  # reward and advantage: microseconds


def test_kernels_are_mosaic_custom_calls_only(events, red):
    ops = events["devices"]["0"]["ops"]
    kinds = {tr.op_kind(o) for o in ops}
    assert {"pallas", "custom-call", "fusion", "while"} <= kinds
    assert not any(tr.is_kernel(o) for o in ops if "AllocateBuffer" in o[0])
    assert red.op_seconds(lambda op, m: tr.is_kernel(op)) == pytest.approx(
        0.020977274, rel=1e-9)


def test_breakdown_names_ops_by_program_and_gaps_by_host_span(red):
    b = red.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0][0] == "jit__lambda:fusion"
    assert b["device_ops"][1][0] == "jit__lambda:pallas"
    assert all(":while" not in k for k, _ in b["device_ops"])
    assert b["idle_gaps"][0] == ["bench.generate", pytest.approx(0.005576064)]
    names = {g[0] for g in b["idle_gaps"]}
    assert names <= {"bench.generate", "bench.logprobs", "bench.reward",
                     "bench.advantage", "bench.actor_step",
                     "between engine calls"}


def test_metric_readers_on_the_fixture(red):
    cell = catalog.load_cell("qwen2.5-7b-grpo-cot")
    counts = {"compiles": 0, "occupied_lane_steps": 3.0, "lane_steps": 8.0,
              "rows": [(100, 300)] * 8}
    step_op = next(o for o in red.devices["0"]["ops"]
                   if tr.is_kernel(o) and "fusion" not in o[0])
    ctx = harness.MetricContext(
        cell=cell, counts=counts, trace=red, window_s=red.window_s, chips=1,
        peaks=catalog.peaks("TPU v5 lite"),
        scopes={"jit_step": {tr.op_name(step_op): "jit(step)/rmsnorm_bwd/x"}})
    got = harness.per_layer_metrics(ctx)
    assert got["slot_occupancy"]["value"] == pytest.approx(3 / 8)
    assert got["window_compiles"]["value"] == 0
    assert got["device_idle_share"]["value"] == pytest.approx(
        1 - 0.094192905 / 0.107)
    assert got["stage_share.logprob"]["value"] == pytest.approx(
        0.092788747 / 0.107)
    assert got["kernel_share.pallas"]["value"] == pytest.approx(
        0.020977274 / 0.107)
    assert 0 <= got["bwd_ref_share"]["value"] < got["stage_share.train"][
        "value"]
    assert 0 < got["iter_mfu"]["value"] < 1
    for v in got.values():
        assert v["unit"] in ("fraction", "count")


def test_hlo_scopes_read_op_name_metadata():
    text = ('  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
            'calls=%fc, metadata={op_name="jit(step)/transpose(jvp('
            'flash_attention_bwd))/dot_general" source_file="x.py"}\n'
            '  ROOT %tuple.1 = (f32[8]{0}) tuple(%fusion.3)\n')
    assert tr.hlo_scopes(text) == {
        "fusion.3": "jit(step)/transpose(jvp(flash_attention_bwd))/dot_general"}
