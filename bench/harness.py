"""One run of one cell: build the program's pipeline through its public
seams, drive the set-up steps, measure the window, check the result against
the plain reference, and reduce everything to the result line.

The harness touches the program only through ``ExperimentSpec.compile``,
``Pipeline.run`` and the entries of ``pipe.ctx.engines``, which it wraps:
the ``"generate"`` engine to pass each sequence's response budget (the
engine's own ``budgets=`` argument) and to record what the timed path
produced in the set-up steps; the ``"logprobs"`` engine to record the
reference-policy log-probs; and, in traced runs, every engine in a
``jax.profiler.TraceAnnotation`` named ``bench.<engine>``.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import pathlib
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import catalog, flops, reference

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
OUT_DIR = CHECKOUT / ".bench_out"
SETUP_STEPS = 3  # the reference follows these; they also warm every shape
TRACE_ITERS = 2  # whole iterations in a traced window
B1 = 0.9  # AdamW's first-moment decay: m after one step is (1 - B1) * g
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
STEP_PROGRAM = "jit_step"  # the actor step's module in a device trace


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# --------------------------------------------------------------------------- #
# traffic, as the program's dataset and as budgets for its engine
# --------------------------------------------------------------------------- #
class Traffic:
    """The cell's traffic stream. The pipeline's dataloader asks the dataset
    for one batch of rows per iteration, in order; each request is served
    the generator's next batch, whose budgets then wait for the generate
    call of the same iteration."""

    def __init__(self, cell: dict, seed: int):
        mix = cell["traffic_spec"]
        gen = catalog.traffic_generator(mix)
        self.group = mix["group_size"]
        self.cycle = gen.cycle(mix)  # iterations in a whole cycle of sizes
        self.prompts_per_iter = cell["prompts_per_iter"]
        self._batches = gen.batches(
            mix, seed=seed, prompts_per_iter=self.prompts_per_iter,
            vocab_size=cell["config_spec"]["layout"]["vocab_size"])
        self.pending: collections.deque = collections.deque()

    def __len__(self) -> int:  # the dataloader's epoch is one batch
        return self.prompts_per_iter

    def get_rows(self, idx):
        if len(idx) != self.prompts_per_iter:
            raise ValueError(f"asked for {len(idx)} rows, the cell serves "
                             f"{self.prompts_per_iter} per iteration")
        batch = next(self._batches)
        self.pending.append(batch)
        return batch.prompts, batch.answers


@dataclasses.dataclass
class Step:
    """What one iteration of the timed path produced, on the host."""

    tokens: np.ndarray
    mask: np.ndarray
    old_lp: np.ndarray
    answers: np.ndarray
    ref_lp: Optional[np.ndarray] = None
    loss: Optional[float] = None


class Recorder:
    """Counts every iteration; keeps host copies of the set-up steps."""

    def __init__(self, group: int):
        self.group = group
        self.capture = False
        self.steps: List[Step] = []
        self.reset_counts()

    def reset_counts(self):
        self.prompt_tokens = 0
        self.response_tokens = 0
        self.occupied_lane_steps = 0.0
        self.lane_steps = 0.0
        self.rows: List[tuple] = []  # (prompt_len, response_len) per row

    def on_generate(self, batch, res, stats):
        lengths = np.asarray(res.lengths)
        true_len = np.repeat(batch.true_len, self.group)
        self.prompt_tokens += int(true_len.sum())
        self.response_tokens += int(lengths.sum())
        self.rows.extend(zip(true_len.tolist(), lengths.tolist()))
        lane = stats["num_slots"] * stats["decode_steps"]
        self.lane_steps += lane
        self.occupied_lane_steps += stats["slot_occupancy"] * lane
        if self.capture:
            self.steps.append(Step(
                tokens=np.asarray(res.tokens), mask=np.asarray(
                    res.response_mask), old_lp=np.asarray(res.old_logprob),
                answers=np.repeat(batch.answers, self.group)))

    def on_logprobs(self, out):
        if self.capture and self.steps and self.steps[-1].ref_lp is None:
            self.steps[-1].ref_lp = np.asarray(out[0])


class Wrapped:
    """An engine with the harness around its calls; every other attribute
    (``last_stats``, ``model``, ...) is the engine's own."""

    def __init__(self, name: str, engine, before=None, after=None,
                 traced: bool = False):
        self._name, self._engine = name, engine
        self._before, self._after, self._traced = before, after, traced
        self.arg_specs = None  # shapes of the last call's arguments

    def __getattr__(self, attr):
        return getattr(self._engine, attr)

    def __call__(self, *args, **kwargs):
        import jax

        if self._before is not None:
            kwargs.update(self._before())
        if self._traced:
            self.arg_specs = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding), args)
            with jax.profiler.TraceAnnotation(f"bench.{self._name}"):
                out = self._engine(*args, **kwargs)
        else:
            out = self._engine(*args, **kwargs)
        if self._after is not None:
            self._after(out)
        return out


# --------------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------------- #
def experiment(cell: dict, seed: int):
    """The cell's ``ExperimentSpec``: the registry's model with the
    configuration file's overrides, and the cell's RL, rollout and
    coordinator fields."""
    from repro.api import ExperimentSpec
    from repro.configs import get_config
    from repro.configs.base import DataCoordinatorConfig, RolloutEngineConfig
    from repro.rl import RLConfig

    prog = cell["config_spec"]["program"]
    model = dataclasses.replace(get_config(prog["registry"]),
                                **prog["overrides"])
    mix = cell["traffic_spec"]
    rl = RLConfig(**dict(cell["rl"], group_size=mix["group_size"],
                         max_new_tokens=mix["max_new"],
                         temperature=mix["temperature"]))
    return ExperimentSpec(
        model=model, rl=rl,
        rollout=RolloutEngineConfig(**cell["rollout"]),
        coordinator=DataCoordinatorConfig(**cell.get("coordinator", {})),
        mesh_shape=tuple(cell["mesh"]),
        prompts_per_iter=cell["prompts_per_iter"], seed=seed)


def check_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                     "the benchmark measures the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def _device_info(devs, mesh_devices) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in mesh_devices)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(mesh_devices), "memory_peak_bytes": int(peak)}


class CompileCounter:
    """Counts executables JAX builds or loads while ``on``."""

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event == COMPILE_EVENT:
            self.count += 1


class GcPauses:
    """Python's collector pauses while entered, through ``gc.callbacks``:
    ``pauses`` holds ``(generation, start, seconds)`` for each."""

    def __init__(self):
        self.pauses: List[tuple] = []
        self._start = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.pauses.append((info["generation"], self._start,
                                time.perf_counter() - self._start))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def prepare(require_tpu: bool, chips: int):
    """Compile cache on, devices checked; returns (devices, peaks)."""
    import jax
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # keep every program, however quick to build, so warm runs load all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not require_tpu:
        return jax.devices(), None
    devs = check_devices(chips)
    return devs, catalog.peaks(devs[0].device_kind)


class Session:
    """The cell's pipeline, built once from the seed, with the harness's
    wrappers on its engines. ``setup`` drives its first steps and records
    them; ``window`` measures; ``release`` frees the program's state."""

    def __init__(self, cell: dict, seed: int, *, traced: bool = False,
                 fault=None):
        self.cell, self.seed = cell, seed
        self.traffic = traffic = Traffic(cell, seed)
        self.pipe = pipe = experiment(cell, seed).compile(dataset=traffic)
        self.mesh_devices = list(pipe.ctx.mesh.devices.flat)
        self.rec = rec = Recorder(traffic.group)
        if fault is not None:  # underneath the harness, where work is done
            fault(pipe)
        engines = pipe.ctx.engines
        gen = engines["generate"]
        engines["generate"] = Wrapped(
            "generate", gen,
            before=lambda: {"budgets": traffic.pending[0].budgets},
            after=lambda res: rec.on_generate(traffic.pending.popleft(), res,
                                              gen.last_stats),
            traced=traced)
        engines["logprobs"] = Wrapped("logprobs", engines["logprobs"],
                                      after=rec.on_logprobs, traced=traced)
        for name in ("reward", "advantage", "actor_step"):
            engines[name] = Wrapped(name, engines[name], traced=traced)

    def setup(self):
        """The same object the window drives, through its first steps:
        records each step, the first gradient as the optimizer got it (from
        m after one step) and each leaf's change after the last."""
        import jax

        pipe, rec = self.pipe, self.rec
        rec.capture = True
        for i in range(SETUP_STEPS):
            history = pipe.run(1)
            rec.steps[-1].loss = float(history[0]["actor/loss"])
            if i == 0:
                self.grad_norms = np.asarray(reference.leaf_norms(
                    pipe.ctx.actor_state.opt.m)) / (1 - B1)
        rec.capture = False
        self.delta_norms = np.asarray(reference.diff_norms(
            pipe.ctx.actor_state.params, pipe.ctx.ref_params))
        self.shapes = [tuple(a.shape) for a in jax.tree.leaves(
            pipe.ctx.actor_state.params)]
        jax.block_until_ready(pipe.ctx.actor_state.params)

    def window(self, seconds: float, iterations: Optional[int] = None):
        """Whole iterations until ``seconds`` have passed and the window
        holds whole cycles of the traffic's sizes (or exactly
        ``iterations``), the last one's update waited for. Returns the
        window's length in seconds and its counts, with each iteration's
        host time, tokens and the collector's pauses inside it."""
        import jax

        pipe, rec, cycle = self.pipe, self.rec, self.traffic.cycle
        counter = CompileCounter()
        rec.reset_counts()
        counter.on = True
        ends, tokens = [], []
        w0 = time.perf_counter()
        with GcPauses() as pauses, jax.profiler.TraceAnnotation(
                "bench.window"):
            while True:
                pipe.run(1)
                ends.append(time.perf_counter())
                tokens.append(rec.prompt_tokens + rec.response_tokens)
                iters = len(ends)
                if (iters >= iterations if iterations else
                        ends[-1] - w0 >= seconds and iters % cycle == 0):
                    break
            jax.block_until_ready(pipe.ctx.actor_state.params)
        window_s = time.perf_counter() - w0
        counter.on = False
        counts = window_counts(rec, iters, counter.count)
        counts["iteration_s"] = np.diff([w0] + ends).tolist()
        counts["iteration_tokens"] = np.diff([0] + tokens).tolist()
        counts["gc_pauses"] = [(int(np.searchsorted(ends, t0)), gen, dt)
                               for gen, t0, dt in pauses.pauses]
        return window_s, counts

    def step_scopes(self) -> Dict[str, Dict[str, str]]:
        """The actor step's instructions and the JAX scopes they came from,
        from its compiled text (the executable the window ran)."""
        from bench import trace as trace_mod

        step = self.pipe.ctx.engines["actor_step"]
        if step.arg_specs is None or not hasattr(step._engine, "lower"):
            return {}
        text = step._engine.lower(*step.arg_specs).compile().as_text()
        return {STEP_PROGRAM: trace_mod.hlo_scopes(text)}

    def program_side(self) -> dict:
        return program_side(self.rec.steps, self.grad_norms, self.delta_norms)

    def release(self):
        """Drop every reference to the program's arrays, so the reference
        can use the chip's memory."""
        self.pipe.ctx.engines.clear()
        self.pipe = None
        gc.collect()


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t0: float, require_tpu: bool = True, cell: Optional[dict] = None,
        fault=None) -> dict:
    """One run of ``workload``; returns the result line's object. ``cell``
    replaces the cell's files (tests at small sizes); ``fault(pipe)`` may
    break the timed path underneath (tests of the comparison)."""
    import jax

    cell = cell or catalog.load_cell(workload)
    devs, peaks = prepare(require_tpu, cell["chips"])
    sess = Session(cell, seed, traced=trace, fault=fault)
    sess.setup()
    setup_s = time.perf_counter() - t0

    trace_dir = None
    if trace:
        trace_dir = OUT_DIR / f"trace-{workload}-{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans: TraceAnnotation only
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    window_s, counts = sess.window(
        seconds, iterations=TRACE_ITERS if trace else None)
    if trace:
        jax.profiler.stop_trace()
        scopes = sess.step_scopes()
    device = _device_info(devs, sess.mesh_devices)
    chips = len(sess.mesh_devices)
    device_ids = [d.id for d in sess.mesh_devices]
    sess.release()

    r0 = time.perf_counter()
    checks = check_against_reference(cell, seed, sess.rec.steps,
                                     sess.grad_norms, sess.delta_norms,
                                     sess.shapes)
    print_iterations(counts)
    print(f"bench: setup {setup_s:.1f} s, window {window_s:.1f} s "
          f"({counts['iterations']} iterations), reference "
          f"{time.perf_counter() - r0:.1f} s", file=sys.stderr)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": counts["iterations"],
              "failed": 0}
    if trace:
        from bench import trace as trace_mod

        red = trace_mod.reduce(trace_mod.collect(trace_dir), device_ids)
        ctx = MetricContext(cell=cell, counts=counts, trace=red,
                            window_s=red.window_s, chips=chips, peaks=peaks,
                            scopes=scopes)
        result["metrics"] = per_layer_metrics(ctx)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        tokens = counts["prompt_tokens"] + counts["response_tokens"]
        result["metrics"] = {
            "tokens_per_s_per_chip": {"value": tokens / window_s / chips,
                                      "unit": "tokens/s/chip"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["device"] = device
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def print_iterations(counts: dict, stream=sys.stderr):
    """Each window iteration's host time, tokens and collector pauses, and
    the pauses by generation: the diagnostics of a window's steadiness."""
    gc_s = collections.defaultdict(float)
    by_gen = collections.defaultdict(lambda: [0, 0.0])
    for i, gen, dt in counts["gc_pauses"]:
        gc_s[i] += dt
        by_gen[gen][0] += 1
        by_gen[gen][1] += dt
    for i, (dt, tok) in enumerate(zip(counts["iteration_s"],
                                      counts["iteration_tokens"])):
        print(f"bench: iteration {i}: {dt:.6f} s, {tok} tokens, "
              f"gc {gc_s[i]:.6f} s", file=stream)
    print("bench: gc pauses in the window: " + (", ".join(
        f"generation {g} {n} for {t:.6f} s"
        for g, (n, t) in sorted(by_gen.items())) or "none"), file=stream)


def window_counts(rec: Recorder, iters: int, compiles: int) -> dict:
    return {"iterations": iters, "compiles": compiles,
            "prompt_tokens": rec.prompt_tokens,
            "response_tokens": rec.response_tokens,
            "occupied_lane_steps": rec.occupied_lane_steps,
            "lane_steps": rec.lane_steps, "rows": list(rec.rows)}


# --------------------------------------------------------------------------- #
# correctness
# --------------------------------------------------------------------------- #
def program_side(steps: List[Step], grad_norms, delta_norms) -> dict:
    return {"mask": [s.mask for s in steps], "old_lp": [s.old_lp for s in steps],
            "ref_lp": [s.ref_lp for s in steps],
            "loss": [s.loss for s in steps], "grad_norms": grad_norms,
            "delta_norms": delta_norms}


def reference_steps(steps: List[Step]) -> List[dict]:
    """What the reference may read of the steps: tokens, masks, answers."""
    return [{"tokens": s.tokens, "mask": s.mask, "answers": s.answers}
            for s in steps]


def check_against_reference(cell, seed, steps, grad_norms, delta_norms,
                            shapes):
    """The numbers compared, each beside its limit from the cell file."""
    import jax

    arch = reference.arch_of(cell["config_spec"])
    want = [tuple(a.shape) for a in jax.tree.leaves(jax.eval_shape(
        lambda: reference.init_params(arch, seed)))]
    if want != shapes:
        raise RuntimeError(f"the program's parameters {shapes} are not the "
                           f"configuration's {want}")
    ref = reference.follow(arch, seed, reference_steps(steps), cell["rl"] | {
        "group_size": cell["traffic_spec"]["group_size"]})
    nums = reference.compare(program_side(steps, grad_norms, delta_norms), ref)
    limits = cell["limits"]
    return {k: {"value": nums[k], "limit": limits[k]} for k in limits}


# --------------------------------------------------------------------------- #
# per-layer metrics
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's reader may read."""

    cell: dict
    counts: dict
    trace: Any
    window_s: float
    chips: int
    peaks: dict
    scopes: Dict[str, Dict[str, str]] = dataclasses.field(
        default_factory=dict)  # program -> instruction -> JAX scope

    def model_flops(self) -> float:
        rows = self.counts["rows"]
        return flops.iteration_flops(self.cell["config_spec"]["layout"],
                                     [p for p, _ in rows], [r for _, r in rows])


def per_layer_metrics(ctx: MetricContext) -> Dict[str, dict]:
    """Every reader that finds something to read in this cell."""
    out = {}
    for name, mod in catalog.metric_readers().items():
        value = mod.read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": mod.UNIT}
    return out


def print_checks(result: dict, stream=sys.stderr):
    for name, c in result.get("checks", {}).items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=stream)
