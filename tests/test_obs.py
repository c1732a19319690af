"""Telemetry subsystem tests (repro.obs; docs/observability.md).

Covers the tracer (null-object fast path, ring buffer, Chrome-trace schema,
thread safety), the metrics registry (bitwise flat-dict back-compat, exact
cross-host histogram merge, quantile accuracy vs numpy), the sinks (stdout
byte-compatibility with the historical train line, JSONL, in-memory), the
worker instrumentation (time/+error/ on a raising stage), fleet snapshot
aggregation + the straggler report, the launch flags, and the ci.sh chunk-
time emission. Property-test versions of the histogram laws live in
tests/test_obs_hypothesis.py (optional dep)."""
import json
import os
import pathlib
import subprocess
import threading

import numpy as np
import pytest

from repro.obs import (
    JSONLSink,
    MemorySink,
    MetricsRegistry,
    StdoutSink,
    Tracer,
    exponential_boundaries,
    get_tracer,
    iteration_record,
    set_tracer,
)
from repro.obs.aggregate import (
    collect_snapshots,
    merge_traces,
    render_report,
    straggler_report,
)
from repro.obs.metrics import Histogram
from repro.obs.trace import NULL_TRACER, _NULL_SPAN

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _isolated_tracer():
    """Every test starts from the disabled global tracer and restores it."""
    prev = set_tracer(None)
    yield
    set_tracer(prev)


# --------------------------------------------------------------------- #
# tracer: null-object path + overhead
# --------------------------------------------------------------------- #
def test_disabled_tracer_is_null_object():
    t = Tracer(enabled=False)
    sp = t.span("x", cat="dag", k=1)
    assert sp is _NULL_SPAN
    with sp as s:
        s.set(error=1)  # no-op, no raise
    t.instant("i")
    assert t.num_events == 0
    assert get_tracer() is NULL_TRACER  # module default is disabled


def test_disabled_tracer_overhead_is_negligible():
    """Acceptance: obs disabled adds no measurable overhead. 100k no-op
    spans must stay comfortably under 10us each even on a loaded CI box
    (the real cost is ~100ns: one method call + a singleton return)."""
    import time

    t = Tracer(enabled=False)
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        with t.span("node/train", cat="dag", node="train"):
            pass
    per_op = (time.perf_counter() - t0) / n
    assert per_op < 10e-6, f"{per_op * 1e6:.2f}us per disabled span"


# --------------------------------------------------------------------- #
# tracer: recording, ring buffer, chrome export
# --------------------------------------------------------------------- #
def test_span_nesting_and_chrome_schema(tmp_path):
    t = Tracer(enabled=True, host=3)
    with t.span("outer", cat="dag", node="gen"):
        with t.span("inner", cat="rollout", lanes=4):
            pass
    t.instant("tick", cat="dag", it=0)
    assert t.num_events == 3

    path = tmp_path / "trace.json"
    t.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        assert e["ph"] in ("X", "M", "i")
        assert e["pid"] == 3
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"outer", "inner"}
    # inner completes first but is nested within outer's interval
    assert xs["outer"]["ts"] <= xs["inner"]["ts"]
    assert xs["outer"]["ts"] + xs["outer"]["dur"] >= (
        xs["inner"]["ts"] + xs["inner"]["dur"])
    assert xs["inner"]["args"]["lanes"] == 4
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and inst[0]["s"] == "p"
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert "host3" in names  # per-host process track
    # one thread track per category
    cats = {e.get("cat") for e in evs if e["ph"] == "X"}
    assert {"dag", "rollout"} <= (names | cats)


def test_ring_buffer_wraparound_drops_oldest():
    t = Tracer(enabled=True, capacity=4)
    for i in range(10):
        with t.span(f"s{i}", cat="dag"):
            pass
    assert t.num_events == 4  # retained = min(total, capacity)
    assert t.dropped == 6
    kept = [e["name"] for e in t.to_events()]
    assert kept == ["s6", "s7", "s8", "s9"]  # oldest-first after wrap


def test_tracer_thread_safety():
    t = Tracer(enabled=True, capacity=1 << 15)
    nthreads, per = 8, 500

    def work(k):
        for i in range(per):
            with t.span(f"t{k}/{i}", cat="dag"):
                pass

    ts = [threading.Thread(target=work, args=(k,)) for k in range(nthreads)]
    [th.start() for th in ts]
    [th.join() for th in ts]
    assert t.num_events == nthreads * per
    assert t.dropped == 0
    assert len(t.to_events()) == nthreads * per


def test_set_tracer_save_restore():
    mine = Tracer(enabled=True)
    prev = set_tracer(mine)
    assert get_tracer() is mine
    set_tracer(prev)
    assert get_tracer() is not mine


def test_enabled_spans_reach_the_profiler_trace_marked(tmp_path):
    """An enabled tracer writes each span into the JAX profiler's trace as
    well as into its ring: named for the span, with its category under
    PROFILER_MARK and its attributes (those set mid-span too) as event
    stats, on the profiler's clock. With no profiler trace running, the
    ring alone records."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.obs.trace import PROFILER_MARK

    t = Tracer(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("rollout/visit", cat="rollout", visit=2) as sp:
            with t.span("rollout/decode", cat="rollout", burst=2):
                pass
            sp.set(completed=5)
    finally:
        jax.profiler.stop_trace()
    with t.span("node/train", cat="dag"):
        pass
    assert t.num_events == 3
    (xplane,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = {e.name: (e.start_ns, e.duration_ns,
                      {k: str(v) for k, v in e.stats})
             for plane in ProfileData.from_file(xplane).planes
             for line in plane.lines for e in line.events
             if PROFILER_MARK in {k for k, _ in e.stats}}
    assert set(found) == {"rollout/visit", "rollout/decode"}
    visit, decode = found["rollout/visit"], found["rollout/decode"]
    assert visit[2] == {PROFILER_MARK: "rollout", "visit": "2",
                        "completed": "5"}
    assert decode[2] == {PROFILER_MARK: "rollout", "burst": "2"}
    assert visit[0] <= decode[0]
    assert decode[0] + decode[1] <= visit[0] + visit[1]


# --------------------------------------------------------------------- #
# metrics registry
# --------------------------------------------------------------------- #
def test_registry_flat_dict_is_bitwise_identity():
    """Acceptance: every existing metrics key survives the registry
    round-trip bitwise. Gauges store values verbatim — including numpy
    scalars and awkward floats — so as_flat_dict() == the input dict."""
    metrics = {
        "actor/loss": 0.1 + 0.2,  # 0.30000000000000004 — must not re-round
        "rollout/tokens": np.float32(16.0),
        "time/train": 1e-9,
        "reward/mean": -0.0,
    }
    reg = MetricsRegistry()
    reg.record_dict(metrics)
    flat = reg.as_flat_dict()
    assert flat == metrics
    for k in metrics:
        assert repr(flat[k]) == repr(metrics[k])  # bitwise, not just ==


def test_registry_counter_and_histogram_keys():
    reg = MetricsRegistry()
    reg.counter("requests").inc()
    reg.counter("requests").inc(2)
    h = reg.histogram("lat_s", boundaries=[1.0, 2.0, 3.0])
    for v in (0.5, 1.5, 2.5, 3.5):
        h.record(v)
    flat = reg.as_flat_dict()
    assert flat["requests"] == 3.0
    assert flat["lat_s/count"] == 4.0
    assert flat["lat_s/mean"] == pytest.approx(2.0)
    assert flat["lat_s/p50"] == pytest.approx(1.5)


def test_histogram_merge_equals_concatenation():
    """The law that makes cross-host aggregation exact: quantiles are a
    pure function of (boundaries, counts, min, max), so merging per-host
    histograms gives IDENTICAL quantiles to one histogram fed everything."""
    rng = np.random.default_rng(7)
    samples = rng.lognormal(mean=-2.0, sigma=1.5, size=3000)
    parts = np.array_split(samples, 3)
    merged = Histogram("h")
    for part in parts:
        h = Histogram("h")
        for v in part:
            h.record(float(v))
        merged.merge(h)
    single = Histogram("h")
    for v in samples:
        single.record(float(v))
    for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
        assert merged.quantile(q) == single.quantile(q)  # exact, not approx
    assert merged.count == single.count == len(samples)
    assert merged.sum == pytest.approx(single.sum)


def test_histogram_merge_rejects_mismatched_boundaries():
    a = Histogram("a", boundaries=[1.0, 2.0])
    b = Histogram("b", boundaries=[1.0, 3.0])
    with pytest.raises(ValueError):
        a.merge(b)


def test_histogram_quantile_within_one_bucket_of_numpy():
    """Dense uniform data: interpolated p50/p99 land within one bucket
    width of numpy's exact (linear-interpolation) quantile."""
    rng = np.random.default_rng(0)
    samples = rng.uniform(0.0, 10.0, size=10_000)
    bounds = list(np.linspace(0.0, 10.0, 101))  # width 0.1
    h = Histogram("u", boundaries=bounds)
    for v in samples:
        h.record(float(v))
    width = bounds[1] - bounds[0]
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(samples, q))
        assert abs(h.quantile(q) - exact) <= width + 1e-9


def test_histogram_quantile_within_one_bucket_of_numpy_lower_sparse():
    """Adversarial sparse data: the one-bucket-width bound holds against
    numpy's method="lower" (the order-statistic the counts actually
    locate; linear interpolation can jump a whole gap between clusters)."""
    samples = np.array([0.0, 0.0, 0.0, 10.0])
    bounds = list(np.linspace(0.0, 10.0, 11))  # width 1.0
    h = Histogram("s", boundaries=bounds)
    for v in samples:
        h.record(float(v))
    for q in (0.5, 0.75, 0.99):
        exact = float(np.quantile(samples, q, method="lower"))
        assert abs(h.quantile(q) - exact) <= 1.0 + 1e-9


def test_histogram_empty_and_clamping():
    h = Histogram("e", boundaries=[1.0, 2.0])
    assert h.quantile(0.5) == 0.0
    h.record(5.0)  # overflow bucket: clamped to observed max
    assert h.quantile(0.99) == 5.0
    assert h.quantile(0.0) == 5.0


def test_histogram_serialization_roundtrip():
    h = Histogram("h", boundaries=[1.0, 2.0])
    for v in (0.5, 1.5, 1.6, 2.5):
        h.record(v)
    h2 = Histogram.from_dict(h.to_dict())
    assert h2.quantile(0.5) == h.quantile(0.5)
    assert h2.count == h.count
    reg = MetricsRegistry()
    reg.counter("c").inc(3)
    reg.record_dict({"g": 1.25})
    reg.histogram("h", boundaries=[1.0, 2.0]).record(1.5)
    reg2 = MetricsRegistry.from_dict(reg.to_dict())
    assert reg2.as_flat_dict() == reg.as_flat_dict()


def test_exponential_boundaries_shape():
    b = exponential_boundaries(1e-3, 1e3, 60)
    assert len(b) == 60
    assert b[0] == pytest.approx(1e-3)
    assert b[-1] == pytest.approx(1e3)
    assert all(x < y for x, y in zip(b, b[1:]))


# --------------------------------------------------------------------- #
# sinks
# --------------------------------------------------------------------- #
def test_stdout_sink_byte_compatible(capsys):
    """Acceptance: the default train line is byte-for-byte the historical
    format (time/* keys stripped, 4-decimal rounding, compact separators
    from json.dumps defaults)."""
    metrics = {"actor/loss": 0.123456, "rollout/tokens": 16.0,
               "time/train": 0.5, "reward/mean": -0.0}
    StdoutSink().emit_iteration(7, metrics, 1.234)
    got = capsys.readouterr().out
    keep = {k: round(v, 4) for k, v in metrics.items()
            if not k.startswith("time/")}
    expected = f"[train] it=7 {1.234:.2f}s {json.dumps(keep)}\n"
    assert got == expected


def test_jsonl_sink_and_iteration_record(tmp_path):
    path = tmp_path / "m.jsonl"
    with JSONLSink(str(path)) as sink:
        sink.write(iteration_record(0, {"a": 1.0, "time/x": 0.1}, 0.5))
        sink.write({"kind": "ci_chunk", "chunk": "c1", "wall_s": 2.0})
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == 2
    assert lines[0]["kind"] == "iteration"
    assert lines[0]["iteration"] == 0
    assert lines[0]["metrics"] == {"a": 1.0, "time/x": 0.1}
    assert lines[1]["chunk"] == "c1"


def test_jsonl_sink_never_opens_unwritten(tmp_path):
    path = tmp_path / "sub" / "m.jsonl"
    sink = JSONLSink(str(path))
    sink.close()  # no write -> no file, no crash on missing parent dir
    assert not path.exists()


def test_memory_sink():
    s = MemorySink()
    s.write({"a": 1})
    s.write({"b": 2})
    assert s.records == [{"a": 1}, {"b": 2}]


# --------------------------------------------------------------------- #
# worker instrumentation: time/ + error/ on a raising stage
# --------------------------------------------------------------------- #
def _bare_worker():
    from repro.configs.base import DataCoordinatorConfig
    from repro.core.worker import DAGWorker

    w = object.__new__(DAGWorker)
    w.coordinator = DataCoordinatorConfig()
    w.buffer = None
    w.ctx = None
    return w


def test_execute_node_records_time_and_error_on_failure():
    """Regression (ISSUE 10 satellite): a raising stage must still record
    time/{node_id}, flag error/{node_id}=1, tag the span, and re-raise."""
    from repro.core.dag import Node, NodeType, Role

    t = Tracer(enabled=True)
    set_tracer(t)
    w = _bare_worker()
    node = Node(node_id="boom", role=Role.ACTOR, type=NodeType.COMPUTE)

    def fn(ctx, buf, node):
        raise RuntimeError("stage exploded")

    metrics = {}
    with pytest.raises(RuntimeError, match="stage exploded"):
        w.execute_node(node, fn, metrics)
    assert metrics["error/boom"] == 1.0
    assert metrics["time/boom"] >= 0.0
    (ev,) = t.to_events()
    assert ev["name"] == "node/boom"
    assert ev["args"]["error"] == 1


def test_execute_node_success_has_no_error_key():
    from repro.core.dag import Node, NodeType, Role

    w = _bare_worker()
    node = Node(node_id="ok", role=Role.ACTOR, type=NodeType.COMPUTE)
    metrics = {}
    w.execute_node(node, lambda c, b, n: {"x": 1.0}, metrics)
    assert metrics["x"] == 1.0
    assert "time/ok" in metrics
    assert not any(k.startswith("error/") for k in metrics)


# --------------------------------------------------------------------- #
# config + spec plumbing
# --------------------------------------------------------------------- #
def test_obs_config_validation():
    from repro.configs.base import ObsConfig

    with pytest.raises(ValueError):
        ObsConfig(ring_capacity=0)
    assert not ObsConfig().enabled  # off by default


def test_experiment_spec_obs_roundtrip_and_legacy():
    from repro.api import ExperimentSpec
    from repro.configs import ARCHS, reduced
    from repro.configs.base import ObsConfig

    spec = ExperimentSpec(model=reduced(ARCHS["qwen2.5-7b"], vocab_size=260),
                          obs=ObsConfig(enabled=True, ring_capacity=128))
    d = spec.to_dict()
    back = ExperimentSpec.from_dict(d)
    assert back.obs == spec.obs
    legacy = spec.to_dict()
    del legacy["obs"]  # pre-obs spec dicts must still load
    assert ExperimentSpec.from_dict(legacy).obs == ObsConfig()


# --------------------------------------------------------------------- #
# pipeline integration: disabled obs is bitwise inert; enabled records
# --------------------------------------------------------------------- #
def _tiny_pipe(obs=None, seed=0):
    from repro.configs import ARCHS, reduced
    from repro.core import build_pipeline
    from repro.rl import RLConfig

    cfg = reduced(ARCHS["qwen2.5-7b"], vocab_size=260, num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                  d_ff=128)
    rl = RLConfig(algorithm="grpo", group_size=2, max_new_tokens=4, lr=1e-4)
    return build_pipeline(cfg, rl, prompts_per_iter=2, seed=seed, obs=obs)


def test_pipeline_obs_disabled_metrics_bitwise_unchanged():
    """Acceptance: with obs disabled (the default), iteration metrics are
    bitwise identical to a build that never heard of obs, and the global
    tracer stays the null tracer."""
    from repro.configs.base import ObsConfig

    m_off = _tiny_pipe(obs=None, seed=3).worker.run_iteration()
    assert get_tracer() is NULL_TRACER
    m_cfg = _tiny_pipe(obs=ObsConfig(enabled=False), seed=3).worker.run_iteration()
    assert get_tracer() is NULL_TRACER
    assert set(m_off) == set(m_cfg)
    for k in m_off:
        if k.startswith("time/"):
            continue  # wall times differ run to run by construction
        assert float(m_off[k]) == float(m_cfg[k]), k


def test_pipeline_obs_enabled_traces_and_registers(tmp_path):
    from repro.configs.base import ObsConfig

    pipe = _tiny_pipe(obs=ObsConfig(enabled=True), seed=3)
    assert pipe.ctx.obs is not None
    metrics = pipe.worker.run_iteration()
    # every stage produced a dag span
    names = {e["name"] for e in pipe.ctx.obs.tracer.to_events()}
    assert any(n.startswith("node/") for n in names)
    # run_iteration fed the registry: flat dict reproduces metrics bitwise
    assert pipe.ctx.obs.registry.as_flat_dict() == metrics
    path = tmp_path / "t.json"
    pipe.ctx.obs.tracer.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert len(doc["traceEvents"]) > 0


# --------------------------------------------------------------------- #
# launch flags: --obs-trace / --obs-metrics
# --------------------------------------------------------------------- #
def test_train_main_obs_flags(tmp_path, capsys):
    from repro.launch import train

    trace = tmp_path / "trace.json"
    mpath = tmp_path / "metrics.jsonl"
    train.main(["--smoke", "--iters", "2", "--prompts-per-iter", "2",
                "--group-size", "2", "--max-new-tokens", "4",
                "--obs-trace", str(trace), "--obs-metrics", str(mpath)])
    out = capsys.readouterr().out
    assert "[train] it=0 " in out  # historical line format intact
    doc = json.loads(trace.read_text())
    cats = {e.get("cat") for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "dag" in cats
    recs = [json.loads(l) for l in mpath.read_text().splitlines()]
    assert [r["iteration"] for r in recs] == [0, 1]
    assert all(r["kind"] == "iteration" for r in recs)
    assert "train/step_s" not in recs[0]["metrics"]  # hist, not a gauge


# --------------------------------------------------------------------- #
# serving latency recording
# --------------------------------------------------------------------- #
def test_record_stream_latency():
    from repro.serving.scheduler import (Request, RequestStream,
                                         record_stream_latency)

    req = Request(rid=1, prompt=[1, 2, 3], max_new=8, arrival=10.0)
    st = RequestStream(req)
    st.append([5], when=10.5, version=0)
    st.append([6, 7], when=11.5, version=0)
    st.finish("eos")
    reg = MetricsRegistry()
    record_stream_latency(reg, st)
    assert reg.histogram("serving/ttft_s").count == 1
    assert reg.histogram("serving/ttft_s").sum == pytest.approx(0.5)
    assert reg.histogram("serving/tpot_s").sum == pytest.approx(0.5)

    rej = RequestStream(Request(rid=2, prompt=[1], max_new=4))
    rej.finish("rejected")
    record_stream_latency(reg, rej)  # rejected: not a latency sample
    assert reg.histogram("serving/ttft_s").count == 1
    record_stream_latency(None, st)  # registry=None is a no-op


# --------------------------------------------------------------------- #
# fleet snapshots + straggler aggregation
# --------------------------------------------------------------------- #
def _publish_synthetic_fleet(tmp_path, host_times):
    """Two FleetContexts over one coordinator dir, publishing per-iteration
    metrics whose time/* sums are the given per-host step times."""
    from repro.configs.base import DistributedConfig
    from repro.distributed.fleet import FleetContext

    coord = str(tmp_path / "coord")
    for h, steps in host_times.items():
        ctx = FleetContext(DistributedConfig(
            num_hosts=max(2, len(host_times)), process_id=h,
            coordinator=coord))
        for it, t in enumerate(steps):
            ctx.publish_metrics(it, {
                "time/generate": t * 0.75,
                "time/train": t * 0.25,
                "actor/loss": 0.5 - 0.01 * it,
            })
    return coord


def test_fleet_snapshot_aggregation_and_straggler_report(tmp_path):
    # host1 is the 2x straggler every iteration
    coord = _publish_synthetic_fleet(
        tmp_path, {0: [1.0, 1.2, 1.1], 1: [2.0, 2.4, 2.2]})
    snaps = collect_snapshots(coord)
    assert sorted(snaps) == [0, 1]
    assert sorted(snaps[0]) == [0, 1, 2]

    report = straggler_report(snaps)
    assert report["hosts"] == [0, 1]
    assert report["slowest_host"] == 1
    assert report["per_host"][1]["total_s"] == pytest.approx(6.6)
    assert report["per_host"][0]["slowest_node"] == "generate"
    it0 = report["per_iteration"][0]
    assert it0["slowest_host"] == 1
    assert it0["max_s"] == pytest.approx(2.0)
    assert it0["skew"] == pytest.approx(2.0 / 1.5)
    assert report["step_hist"]["count"] == 6
    assert report["max_skew"] >= 1.0

    text = render_report(report)
    assert "per-host summary" in text
    assert "host0" in text and "host1" in text
    assert "fleet step-time p50" in text


def test_snapshot_sum_matches_hosts_own_metrics(tmp_path):
    """Acceptance: the straggler table's per-host step time sums to the
    hosts' own time/* metrics exactly (the snapshot is the metrics dict)."""
    host_times = {0: [0.5, 0.7], 1: [0.9, 0.3]}
    coord = _publish_synthetic_fleet(tmp_path, host_times)
    report = straggler_report(collect_snapshots(coord))
    for h, steps in host_times.items():
        for it, t in enumerate(steps):
            assert report["per_host"][h]["step_times"][it] == pytest.approx(
                t, rel=1e-12)


def test_collect_snapshots_skips_torn_writes(tmp_path):
    coord = _publish_synthetic_fleet(tmp_path, {0: [1.0]})
    torn = pathlib.Path(coord) / "obs" / "host0" / "it000099.json"
    torn.write_text('{"host": 0, "iter')  # partial write
    snaps = collect_snapshots(coord)
    assert sorted(snaps[0]) == [0]  # torn file ignored, good one kept


def test_merge_traces(tmp_path):
    t0 = Tracer(enabled=True, host=0)
    with t0.span("a", cat="dag"):
        pass
    t1 = Tracer(enabled=True, host=1)
    with t1.span("b", cat="fleet"):
        pass
    p0, p1 = tmp_path / "t0.json", tmp_path / "t1.json"
    t0.export_chrome(str(p0))
    t1.export_chrome(str(p1))
    out = tmp_path / "merged.json"
    merged = merge_traces([str(p0), str(p1)], str(out))
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert pids == {0, 1}
    assert json.loads(out.read_text()) == merged


def test_obs_report_cli(tmp_path, capsys):
    from repro.launch import obs_report

    coord = _publish_synthetic_fleet(tmp_path, {0: [1.0], 1: [3.0]})
    obs_report.main(["--coordinator", coord])
    out = capsys.readouterr().out
    assert "per-host summary" in out
    assert "host1" in out
    obs_report.main(["--coordinator", coord, "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert doc["slowest_host"] == 1


# --------------------------------------------------------------------- #
# ci.sh chunk-time JSONL emission
# --------------------------------------------------------------------- #
def test_ci_sh_emits_chunk_times_jsonl(tmp_path):
    good = tmp_path / "test_good.py"
    good.write_text("def test_ok():\n    assert True\n")
    jsonl = tmp_path / "ci_times.jsonl"
    env = dict(os.environ, CI_CHUNKS=str(good), CI_OBS_JSONL=str(jsonl))
    env.pop("PYTHONPATH", None)
    res = subprocess.run(
        ["bash", str(REPO / "scripts" / "ci.sh")],
        capture_output=True, text=True, env=env, cwd=REPO,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    assert "[ci] chunk times ->" in res.stdout
    recs = [json.loads(l) for l in jsonl.read_text().splitlines()]
    assert [r["chunk"] for r in recs] == ["chunk1"]
    assert recs[0]["kind"] == "ci_chunk"
    assert recs[0]["wall_s"] >= 0.0


# --------------------------------------------------------------------- #
# benchmarks/report.py obs table over the committed sample trace
# --------------------------------------------------------------------- #
def test_report_obs_table_renders_sample_trace():
    from benchmarks import report as bench_report

    table = bench_report.obs_table()
    assert "| host | subsystem | spans | busy ms |" in table
    assert "host0" in table and "dag" in table
