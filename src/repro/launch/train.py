"""Production RL training driver.

Compiles an :class:`repro.api.ExperimentSpec` for ``--arch`` on the requested
mesh, runs ``--iters`` RL iterations with periodic sharded checkpoints, and
resumes (elastically — any topology) from ``--resume``. A full experiment can
also be loaded from a JSON file (``--experiment spec.json``, the
``ExperimentSpec.to_json`` form) and dumped with ``--dump-experiment``.

On real hardware this runs once per host under ``jax.distributed``; on this
CPU container it drives the same code path on a local mesh (used by the
examples and the convergence benchmark).

Usage:
  python -m repro.launch.train --arch qwen2.5-7b --algorithm grpo \
      --iters 500 --ckpt-dir ckpts/ [--resume ckpts/] [--smoke]
  python -m repro.launch.train --experiment exp.json --iters 100
  python -m repro.launch.train --smoke --max-staleness 1   # async pipeline v2
"""
from __future__ import annotations

import argparse
import dataclasses
import time

from repro.api import ExperimentSpec
from repro.configs import (
    AsyncPipelineConfig,
    DistributedConfig,
    EnvConfig,
    RolloutEngineConfig,
    get_config,
    reduced,
)
from repro.distributed import sharding as shr
from repro.ft import checkpoint
from repro.launch.mesh import init_distributed, make_fleet_mesh, make_local_mesh
from repro.rl import RLConfig, list_algorithms
from repro.rl.trainer import TrainState
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.jax_compat import make_compat_mesh, use_mesh


def build_experiment(args) -> ExperimentSpec:
    """CLI flags -> ExperimentSpec (or load one wholesale from JSON)."""
    if args.experiment:
        with open(args.experiment) as f:
            exp = ExperimentSpec.from_json(f.read())
        if args.max_staleness is not None:
            # CLI overrides the file, like the usage line documents — don't
            # let the flag be silently swallowed by the JSON's setting
            exp = dataclasses.replace(
                exp,
                async_pipeline=AsyncPipelineConfig(
                    enabled=True, max_staleness=args.max_staleness
                ),
            )
        return exp
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg, vocab_size=260, num_layers=2)
    rl = RLConfig(
        algorithm=args.algorithm,
        group_size=args.group_size,
        max_new_tokens=args.max_new_tokens,
        lr=args.lr,
    )
    dag = None
    if args.dag_json:
        from repro.core import DAG

        dag = DAG.from_json(args.dag_json).to_spec()
    async_pipeline = AsyncPipelineConfig()
    if args.max_staleness is not None:
        async_pipeline = AsyncPipelineConfig(
            enabled=True, max_staleness=args.max_staleness
        )
    rollout = RolloutEngineConfig()
    if args.rollout_slots is not None:
        rollout = RolloutEngineConfig(
            engine="continuous", num_slots=args.rollout_slots
        )
    env = EnvConfig()
    if args.env:
        env = EnvConfig(name=args.env, max_turns=args.max_turns,
                        turn_budget=args.turn_budget)
        if env.max_turns > 1 and rollout.engine != "continuous":
            # the episode loop lives in the continuous engine; default the
            # slot pool to one slot per sequence unless --rollout-slots set
            rollout = RolloutEngineConfig(engine="continuous", num_slots=0)
    distributed = None
    if args.num_hosts > 1:
        distributed = DistributedConfig(
            num_hosts=args.num_hosts,
            process_id=args.process_id,
            coordinator=args.coordinator or "",
            grad_compression=args.grad_compression,
        )
    return ExperimentSpec(
        model=cfg,
        rl=rl,
        async_pipeline=async_pipeline,
        rollout=rollout,
        env=env,
        distributed=distributed,
        prompts_per_iter=args.prompts_per_iter,
        centralized=args.centralized_baseline,
        seed=args.seed,
        dag=dag,
    )


def main(argv=None):
    """Run the driver; returns ``(pipeline, per-iteration metrics)`` for
    callers that drive it in-process (``chip_smoke.py``), or None after
    ``--dump-experiment``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-7b")
    ap.add_argument("--algorithm", choices=list_algorithms(), default="grpo")
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--prompts-per-iter", type=int, default=8)
    ap.add_argument("--group-size", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default=None)
    ap.add_argument("--centralized-baseline", action="store_true",
                    help="run the single-controller arm (comparisons)")
    ap.add_argument("--max-staleness", type=int, default=None,
                    help="enable the async off-policy pipeline with this "
                         "staleness bound (0 = lockstep scheduler, bitwise-"
                         "identical to sync; see docs/async_pipeline.md)")
    ap.add_argument("--rollout-slots", type=int, default=None,
                    help="enable the continuous-batching rollout engine "
                         "with this many decode slots (0 = one per "
                         "sequence; see docs/rollout_engine.md)")
    ap.add_argument("--env", default=None,
                    help="registered environment name (repro.rl.envs: "
                         "function_reward | calculator | dialog); enables "
                         "the env/reward subsystem (docs/environments.md)")
    ap.add_argument("--max-turns", type=int, default=1,
                    help="episode turn cap for --env (>1 auto-enables the "
                         "continuous rollout engine's episode loop)")
    ap.add_argument("--turn-budget", type=int, default=0,
                    help="per-turn response-token cap for --env "
                         "(0 = --max-new-tokens)")
    ap.add_argument("--num-hosts", type=int, default=1,
                    help="host processes in the fleet; >1 enables the "
                         "multi-host runtime (docs/multihost.md) — launch "
                         "one copy of this driver per host")
    ap.add_argument("--process-id", type=int, default=0,
                    help="this host's rank in [0, --num-hosts)")
    ap.add_argument("--coordinator", default=None,
                    help="shared coordinator directory (simulated fleet) or "
                         "host:port (jax.distributed on real hardware)")
    ap.add_argument("--grad-compression", choices=["none", "int8_ef"],
                    default="none",
                    help="DP gradient exchange encoding: none = exact fp32 "
                         "(bitwise parity with single-host), int8_ef = "
                         "block-int8 + error feedback (~1/4 wire bytes)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced model config (CPU-sized)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dag-json", default=None,
                    help="custom DAG config file (paper §4.1)")
    ap.add_argument("--experiment", default=None,
                    help="ExperimentSpec JSON file; overrides the arch/rl flags")
    ap.add_argument("--dump-experiment", default=None,
                    help="write the resolved ExperimentSpec JSON here and exit")
    ap.add_argument("--obs-trace", default=None, metavar="PATH",
                    help="enable telemetry and export a Chrome-trace JSON "
                         "here at the end of the run (docs/observability.md)")
    ap.add_argument("--obs-metrics", default=None, metavar="PATH",
                    help="enable telemetry and append per-iteration metrics "
                         "as JSONL here")
    args = ap.parse_args(argv)

    exp = build_experiment(args)
    if args.obs_trace or args.obs_metrics:
        # flags layer on top of whatever the spec (file or CLI) carries,
        # same precedence as --max-staleness
        exp = dataclasses.replace(exp, obs=dataclasses.replace(
            exp.obs,
            enabled=True,
            trace_path=args.obs_trace or exp.obs.trace_path,
            metrics_path=args.obs_metrics or exp.obs.metrics_path,
        ))
    if args.dump_experiment:
        with open(args.dump_experiment, "w") as f:
            f.write(exp.to_json())
        print(f"[train] wrote {args.dump_experiment}")
        return
    enable_compile_cache()
    cfg = exp.model
    dist = exp.distributed
    fleet_ctx = None
    if dist is not None and dist.enabled:
        fleet_ctx = init_distributed(
            dist.coordinator, dist.num_hosts, dist.process_id,
            grad_compression=dist.grad_compression,
        )
        mesh = make_fleet_mesh(dist.num_hosts, dist.devices_per_host)
        if fleet_ctx is not None:
            fleet_ctx.start_heartbeats()
            fleet_ctx.barrier("startup")
    elif exp.mesh_shape:
        mesh = make_compat_mesh(tuple(exp.mesh_shape), tuple(exp.mesh_axes))
    else:
        mesh = make_local_mesh()

    with use_mesh(mesh):
        pipe = exp.compile(mesh=mesh)
        start = 0
        if args.resume:
            state = pipe.ctx.actor_state
            pspecs = shr.param_specs(cfg, mesh, state.params)
            specs = TrainState(params=pspecs, opt=shr.opt_state_specs(pspecs))
            restored, start = checkpoint.restore(
                args.resume, state, mesh=mesh, specs=specs
            )
            pipe.ctx.actor_state = restored
            print(f"[train] resumed from {args.resume} at iteration {start}")

        from repro.obs import JSONLSink, StdoutSink, iteration_record

        obs_rt = getattr(pipe.ctx, "obs", None)
        stdout_sink = StdoutSink()
        jsonl_sink = (JSONLSink(obs_rt.cfg.metrics_path)
                      if obs_rt is not None and obs_rt.cfg.metrics_path
                      else None)
        history = []
        for it in range(start, args.iters):
            if fleet_ctx is not None:
                fleet_ctx.heartbeat(it)
            t0 = time.perf_counter()
            metrics = pipe.worker.run_iteration()
            dt = time.perf_counter() - t0
            history.append(metrics)
            if it % 5 == 0 or it == args.iters - 1:
                stdout_sink.emit_iteration(it, metrics, dt)
            if obs_rt is not None:
                obs_rt.registry.histogram("train/step_s").record(dt)
                if jsonl_sink is not None:
                    jsonl_sink.write(iteration_record(it, metrics, dt))
                if fleet_ctx is not None and obs_rt.cfg.fleet_snapshots:
                    fleet_ctx.publish_metrics(it, metrics)
            if args.ckpt_dir and (it + 1) % args.ckpt_every == 0:
                checkpoint.save(args.ckpt_dir, pipe.ctx.actor_state, step=it + 1)
                print(f"[train] checkpoint @ {it + 1} -> {args.ckpt_dir}")
        if jsonl_sink is not None:
            jsonl_sink.close()
        if obs_rt is not None and obs_rt.cfg.trace_path:
            obs_rt.tracer.export_chrome(obs_rt.cfg.trace_path)
            print(f"[train] wrote trace {obs_rt.cfg.trace_path} "
                  f"({obs_rt.tracer.num_events} events)")
        print(f"[train] done; buffer stats: {pipe.buffer.stats}")
    return pipe, history


if __name__ == "__main__":
    main()
