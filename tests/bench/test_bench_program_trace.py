"""The program's side of a traced run (``bench/program_trace.py``): the
spans ``repro.obs`` writes into the profiler's trace, the exact split of
idle device time by the innermost program span, and the decode burst's
time by the model's named scopes, on synthetic events, on a tiny pipeline
traced on the CPU and on a window recorded on a TPU v5e."""
import dataclasses
import gzip
import json
import pathlib

import pytest

from bench import catalog, harness, program_trace
from bench import trace as tr

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
NEW = ("decode_share.mlp", "decode_share.mixer", "decode_share.head")
MARK = program_trace.MARK
BURST = "jit(burst)/while/body/while/body"
LEAF = "%{} = bf16[8]{{0}} fusion(bf16[8]{{0}} %p), kind=kLoop, calls=%fc"
KERNEL = ('%{} = f32[8]{{0}} custom-call(bf16[8]{{0}} %p), '
          'custom_call_target="tpu_custom_call"')


def _synthetic(scoped=True):
    """A 1000 ns window: a decode burst on [100, 400], an actor step on
    [600, 900], and program spans that open and close inside idle gaps,
    one of them begun before the window."""
    def scope(s):
        return {"scope": s} if scoped else {}

    ops = [
        ["%while.1 = (bf16[8]{0}) while((bf16[8]{0}) %t), condition=%c, "
         "body=%b", 100, 300, {}],
        [LEAF.format("fusion.1"), 100, 150,
         scope(f"{BURST}/mlp/dot_general" if scoped else "")],
        [LEAF.format("fusion.2"), 250, 50, scope(f"{BURST}/mixer/dot_general")],
        [KERNEL.format("body.3"), 300, 50,
         scope("jit(burst)/while/body/head/pallas_call")],
        [LEAF.format("copy.4").replace("fusion(", "copy("), 350, 50,
         scope("jit(burst)/while/body")],
        [LEAF.format("fusion.9"), 600, 300,
         scope("jit(step)/transpose(jvp())/while/body/mlp/dot_general")],
    ]
    modules = [["jit_burst(1)", 100, 300, {}], ["jit_step(2)", 600, 300, {}]]
    spans = [["bench.window", 0, 1000], ["bench.generate", 25, 420]]

    def span(name, cat, start, end):
        return [name, start, end - start, {MARK: cat}]

    program = [
        span("node/reference_inference", "dag", -30, 10),
        span("node/actor_generation", "dag", 20, 450),
        span("prompts/next", "dag", 30, 60),
        span("rollout/visit", "rollout", 70, 420),
        span("rollout/decode", "rollout", 95, 110),
        span("rollout/assemble", "rollout", 420, 440),
        span("node/actor_train", "dag", 500, 950),
    ]
    return {"devices": {"0": {"ops": ops, "modules": modules}},
            "spans": spans, "program_spans": program}


def test_idle_split_cuts_each_gap_at_span_boundaries():
    red = program_trace.reduce(_synthetic(), [0])
    idle = 1 - red.busy_s / red.window_s
    assert idle == pytest.approx(0.4)
    split = {k: v / red.window_s for k, v in
             program_trace.idle_split(red).items()}
    # gap [0, 100]: 10 under the span begun before the window, 10 under
    # none, 10 + 30 + 10 under the generation node and prompts/next, 25 +
    # 5 under the visit and the decode dispatch; gap [400, 600]: 20 visit,
    # 20 assemble, 10 generation node, 50 none, 100 node/actor_train; gap
    # [900, 1000]: 50 node/actor_train, 50 none
    assert split == pytest.approx({"rollout_host": 0.07, "dag_host": 0.22,
                                   "outside_spans": 0.11})
    assert sum(split.values()) == pytest.approx(idle, abs=1e-12)
    by_span = program_trace.idle_split(
        red, key=lambda s: s[0] if s else None)
    assert by_span["prompts/next"] == pytest.approx(30e-9)
    assert by_span["rollout/decode"] == pytest.approx(5e-9)
    assert by_span[None] == pytest.approx(110e-9)


def test_innermost_span_is_the_latest_begun():
    spans = [["a", 0, 100, {}], ["b", 10, 50, {}], ["c", 10, 20, {}]]
    segs = program_trace.innermost_segments(spans, 0, 120)
    assert [(a, b, s and s[0]) for a, b, s in segs] == [
        (0, 10, "a"), (10, 30, "c"), (30, 60, "b"), (60, 100, "a"),
        (100, 120, None)]


def _context(red):
    counts = {"compiles": 0, "occupied_lane_steps": 3.0, "lane_steps": 8.0,
              "rows": [(100, 300)] * 8}
    return harness.MetricContext(
        cell=catalog.load_cell("qwen2.5-7b-grpo-cot"), counts=counts,
        trace=red, window_s=red.window_s, chips=1,
        peaks=catalog.peaks("TPU v5 lite"))


def test_decode_shares_read_the_ops_name_stacks():
    red = program_trace.reduce(_synthetic(), [0])
    got = harness.per_layer_metrics(_context(red))
    # the burst's leaf ops: mlp 150, mixer 50, the sampler kernel 50 of
    # 1000 ns; the while loop holds them and the step's mlp is not decode
    assert got["decode_share.mlp"]["value"] == pytest.approx(0.15)
    assert got["decode_share.mixer"]["value"] == pytest.approx(0.05)
    assert got["decode_share.head"]["value"] == pytest.approx(0.05)
    assert got["stage_share.generate"]["value"] == pytest.approx(0.3)
    assert got["device_idle_share"]["value"] == pytest.approx(0.4)


@pytest.mark.parametrize("scoped", [False, True])
def test_decode_shares_are_absent_without_layer_scopes(scoped):
    events = _synthetic(scoped)
    if scoped:  # name stacks, but none of the model's layer scopes
        for op in events["devices"]["0"]["ops"]:
            if "scope" in op[3]:
                op[3]["scope"] = "jit(burst)/while/body/dot_general"
    red = program_trace.reduce(events, [0])
    got = harness.per_layer_metrics(_context(red))
    assert not set(NEW) & set(got)
    assert got["stage_share.generate"]["value"] == pytest.approx(0.3)


def test_new_readers_find_nothing_in_the_committed_fixture():
    with gzip.open(FIXTURES / "trace_qwen_v5e.json.gz", "rt") as f:
        red = tr.reduce(json.load(f), [0])
    got = harness.per_layer_metrics(_context(red))
    assert not set(NEW) & set(got)
    assert got["device_idle_share"]["value"] == pytest.approx(
        1 - 0.094192905 / 0.107)
    assert program_trace.reduction_for(_context(red)) is None


def test_readers_find_the_traced_runs_events_by_its_window(monkeypatch,
                                                          tmp_path):
    """The harness's reduction drops the name stacks; a reader reads the
    run's trace directory again, the one whose window is the reduction's."""
    events = _synthetic()
    other = json.loads(json.dumps(events))
    other["spans"][0][2] = 900  # another run's window
    for name, ev in (("trace-qwen2.5-7b-grpo-cot-1", other),
                     ("trace-qwen2.5-7b-grpo-cot-2", events),
                     ("trace-another-cell-3", events)):
        (tmp_path / name).mkdir()
        (tmp_path / name / "events.json").write_text(json.dumps(ev))
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    monkeypatch.setattr(program_trace, "collect", lambda d: json.loads(
        (d / "events.json").read_text()))
    program_trace._find.cache_clear()
    try:
        plain = tr.reduce(events, [0])  # what the harness hands a reader
        found = program_trace.reduction_for(_context(plain))
        assert isinstance(found, program_trace.ProgramReduction)
        assert found.window == plain.window
        assert len(found.program_spans) == 7
        got = harness.per_layer_metrics(_context(plain))
        assert got["decode_share.mlp"]["value"] == pytest.approx(0.15)
        shifted = tr.reduce(dict(events, spans=[["bench.window", 0, 990]]),
                            [0])
        assert program_trace.reduction_for(_context(shifted)) is None
    finally:
        program_trace._find.cache_clear()


def test_program_spans_share_the_profilers_clock(tiny, monkeypatch,
                                                 tmp_path):
    """A tiny pipeline with obs enabled, traced on the CPU: the program's
    spans reach the profiler's trace, each engine call inside its stage's
    node span and each engine span inside the harness's engine call."""
    import jax

    from repro.configs.base import ObsConfig
    from repro.obs import set_tracer

    build = harness.experiment
    monkeypatch.setattr(harness, "experiment", lambda cell, seed: (
        dataclasses.replace(build(cell, seed), obs=ObsConfig(enabled=True))))
    prev = set_tracer(None)
    try:
        sess = harness.Session(tiny("dense"), 11, traced=True)
        sess.pipe.run(1)  # compiles outside the trace
        jax.profiler.start_trace(str(tmp_path))
        sess.window(0, iterations=2)
        jax.profiler.stop_trace()
        events = program_trace.collect(tmp_path)
    finally:
        set_tracer(prev)
    spans = events["program_spans"]
    names = {s[0] for s in spans}
    assert {"node/actor_generation", "node/actor_train", "rollout/generate",
            "rollout/visit", "rollout/assemble", "rollout/prefill",
            "rollout/decode", "prompts/next"} <= names
    assert all(s[3][MARK] in ("dag", "rollout") for s in spans)
    assert all("visit" in s[3] and "completed" in s[3]
               for s in spans if s[0] == "rollout/visit")

    def inside(a, b):
        return b[1] <= a[1] and a[1] + a[2] <= b[1] + b[2]

    bench = events["spans"]
    (window,) = [s for s in bench if s[0] == tr.WINDOW_SPAN]
    assert all(inside(s, window) for s in spans)
    generate = [s for s in bench if s[0] == "bench.generate"]
    assert len(generate) == 2
    for s in spans:
        if s[0].startswith("rollout/"):
            assert any(inside(s, g) for g in generate), s
    nodes = [s for s in spans if s[0].startswith("node/")]
    for g in bench:
        if g is not window:
            assert any(inside(g, n) for n in nodes), g
    prompts = [s for s in spans if s[0] == "prompts/next"]
    assert len(prompts) == 2 and all(
        any(inside(p, n) for n in nodes if n[0] == "node/actor_generation")
        for p in prompts)


def test_readers_on_a_window_recorded_with_obs_on():
    """A TPU v5e window of ``qwen2.5-7b-grpo-cot`` traced with obs enabled:
    the first actor step's last 3 ms, the host between iterations, the
    next iteration's prompts, prefill and first 12 ms of decode burst."""
    with gzip.open(FIXTURES / "trace_qwen_v5e_spans.json.gz", "rt") as f:
        red = program_trace.reduce(json.load(f), [0])
    got = {k: v["value"] for k, v in
           harness.per_layer_metrics(_context(red)).items()}
    split = {k: v / red.window_s for k, v in
             program_trace.idle_split(red).items()}
    assert sum(split.values()) == pytest.approx(
        got["device_idle_share"], abs=1e-6)
    assert split == pytest.approx({"dag_host": 0.4053103239159293,
                                   "rollout_host": 0.028422625102830154,
                                   "outside_spans": 0.0030879116606415927},
                                  rel=1e-9)
    decode = {m: got[m] for m in NEW}
    assert decode == pytest.approx({"decode_share.mlp": 0.1421729797613483,
                                    "decode_share.mixer": 0.02427608899312881,
                                    "decode_share.head": 0.02314050320869963},
                                   rel=1e-9)
    assert max(decode, key=decode.get) == "decode_share.mlp"
    assert sum(decode.values()) <= got["stage_share.generate"]
    names = [s[0] for s in red.program_spans]
    assert {"node/actor_train", "node/actor_generation", "prompts/next",
            "rollout/visit", "rollout/prefill", "rollout/decode"} <= set(names)
