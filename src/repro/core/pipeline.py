"""Built-in RL pipelines (paper Fig. 1) + the end-to-end driver.

``build_pipeline`` is a thin compiler over specs: it resolves the
:class:`~repro.rl.algorithms.AlgorithmSpec` for ``rl.algorithm`` (or takes one
directly), wires together every subsystem — model init, jitted engines, the
DAG (the spec's template or user-supplied), the planner's serialized chain,
the Data Coordinator (Distributed Dataloader + Databuffer), and a DAG Worker.
No layer below this point ever inspects the algorithm *name*; they only see
the spec's callables. ``centralized=True`` swaps in the single-controller
databuffer — the baseline arm for the paper's comparisons.

The user-facing entry point is :class:`repro.api.ExperimentSpec`, whose
``compile()`` lands here.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import (
    AsyncPipelineConfig,
    DataCoordinatorConfig,
    DistributedConfig,
    EnvConfig,
    ModelConfig,
    RolloutEngineConfig,
)
from repro.core.dag import DAG
from repro.core.databuffer import (
    CentralizedDatabuffer,
    DistributedDatabuffer,
    DoubleBufferedDatabuffer,
)
from repro.core.planner import DAGPlanner
from repro.core.registry import Registry, default_registry
from repro.core.worker import DAGWorker, WorkerContext
from repro.data.dataloader import DistributedDataloader
from repro.data.dataset import SyntheticMathDataset
from repro.data.tokenizer import ByteTokenizer
from repro.models import get_model
from repro.rl import critic as critic_mod
from repro.rl import rollout as rollout_mod
from repro.rl import trainer
from repro.rl.trainer import RLConfig


# --------------------------------------------------------------------------- #
# built-in DAGs (paper Fig. 1) — re-exported from the algorithm registry for
# backward compatibility; the templates now live with their specs.
# --------------------------------------------------------------------------- #
def grpo_dag() -> DAG:
    from repro.rl import algorithms

    return algorithms.grpo_dag()


def ppo_dag() -> DAG:
    from repro.rl import algorithms

    return algorithms.ppo_dag()


# --------------------------------------------------------------------------- #
def _build_engines(model, cfg: ModelConfig, rl: RLConfig, tok: ByteTokenizer,
                   spec, rollout: Optional[RolloutEngineConfig] = None,
                   env_runtime=None):
    """Jitted engines for one algorithm spec. The advantage engine comes from
    ``spec.make_advantage``; critic engines exist iff the spec uses a critic.
    The GENERATE engine is either the jitted lockstep ``rollout.generate`` or
    the slot-refill :class:`~repro.rl.rollout_engine.ContinuousRolloutEngine`
    (``RolloutEngineConfig.engine == "continuous"``) — same call contract,
    same RolloutResult. An ``env_runtime`` turns the continuous engine's slot
    loop into the multi-turn episode loop (docs/environments.md)."""
    from repro.rl import envs as envs_mod

    eng: Dict[str, Any] = {}

    def _generate(params, prompts, key):
        return rollout_mod.generate(
            model, params, prompts, key,
            max_new=rl.max_new_tokens, temperature=rl.temperature,
            eos_id=tok.eos_id, pad_id=tok.pad_id,
        )

    if rollout is not None and rollout.engine == "continuous":
        from repro.rl.rollout_engine import ContinuousRolloutEngine

        env_kw = {}
        if env_runtime is not None:
            env_kw = dict(
                env=env_runtime,
                max_turns=env_runtime.cfg.max_turns,
                turn_budget=env_runtime.cfg.turn_budget,
                obs_budget=env_runtime.cfg.obs_budget,
            )
        eng["generate"] = ContinuousRolloutEngine(
            model,
            max_new=rl.max_new_tokens,
            temperature=rl.temperature,
            eos_id=tok.eos_id,
            pad_id=tok.pad_id,
            num_slots=rollout.num_slots,
            prefill_chunk=rollout.prefill_chunk,
            prefill_bucket=rollout.prefill_bucket,
            refill_threshold=rollout.refill_threshold,
            **env_kw,
        )
    else:
        eng["generate"] = jax.jit(_generate)
    eng["logprobs"] = jax.jit(lambda p, t: model.logprobs(p, t))
    # the REWARD stage's scorer is resolved from the reward registry (the
    # default "math" is exactly the pre-registry math_reward_tokens path)
    reward_name = env_runtime.cfg.reward if env_runtime is not None else "math"
    token_fn = envs_mod.get_reward(reward_name).token_fn
    eng["reward"] = jax.jit(
        lambda tokens, mask, answers: token_fn(tokens, mask, answers, tok)
    )
    eng["advantage"] = jax.jit(spec.make_advantage(rl))
    if spec.uses_critic:
        eng["values"] = jax.jit(
            lambda p, t: critic_mod.values_fn(model.cfg, p, t)
        )
        eng["critic_step"] = jax.jit(trainer.make_critic_step(model.cfg, rl))
    eng["actor_step"] = jax.jit(trainer.make_actor_step(model, rl,
                                                        algorithm=spec))
    return eng


@dataclasses.dataclass
class Pipeline:
    worker: DAGWorker
    ctx: WorkerContext
    buffer: DistributedDatabuffer
    dag: DAG
    plan: Any

    def run(self, iterations: int):
        history = []
        for _ in range(iterations):
            history.append(self.worker.run_iteration())
        return history


def build_pipeline(
    cfg: ModelConfig,
    rl: RLConfig,
    *,
    mesh: Optional[Mesh] = None,
    dag: Optional[DAG] = None,
    dataset=None,
    prompts_per_iter: int = 8,
    centralized: bool = False,
    coordinator: Optional[DataCoordinatorConfig] = None,
    async_pipeline: Optional[AsyncPipelineConfig] = None,
    rollout: Optional[RolloutEngineConfig] = None,
    env: Optional[EnvConfig] = None,
    distributed: Optional[DistributedConfig] = None,
    obs=None,
    registry: Optional[Registry] = None,
    algorithm=None,
    seed: int = 0,
) -> Pipeline:
    from repro.rl import algorithms
    from repro.rl import envs as envs_mod

    spec = algorithm or algorithms.get_algorithm(rl.algorithm)
    coordinator = coordinator or DataCoordinatorConfig()
    if distributed is not None and distributed.enabled:
        if centralized:
            raise ValueError(
                "a multi-host fleet has no single controller to centralize "
                "through; distributed cannot be combined with centralized=True"
            )
        if async_pipeline is not None and async_pipeline.enabled:
            raise ValueError(
                "the fleet gradient exchange is a per-iteration collective; "
                "combine it with the async pipeline once the exchange is "
                "staleness-aware (not yet supported)"
            )
        if mesh is None:
            from repro.launch.mesh import make_fleet_mesh

            mesh = make_fleet_mesh(
                distributed.num_hosts, distributed.devices_per_host
            )
    if mesh is None:
        from repro.launch.mesh import make_compat_mesh

        mesh = make_compat_mesh((1, 1), ("data", "model"))
    tok = ByteTokenizer()
    assert cfg.vocab_size >= tok.vocab_size, "model vocab must cover the tokenizer"
    model = get_model(cfg)

    env_runtime = None
    if env is not None and env.enabled:
        if env.max_turns > 1 and (rollout is None
                                  or rollout.engine != "continuous"):
            raise ValueError(
                "multi-turn environments need the continuous rollout "
                "engine's episode loop: set RolloutEngineConfig("
                "engine='continuous') (single-turn envs run on either engine)"
            )
        env_runtime = envs_mod.EnvRuntime(envs_mod.get_env(env.name), env, tok)

    key = jax.random.PRNGKey(seed)
    k_actor, k_critic, k_run = jax.random.split(key, 3)
    # Data-parallel placement: every device of the mesh holds the whole
    # actor, reference and optimizer state (replicated), and the batch is
    # split over the data axes. FSDP layouts (sharding.param_specs) are
    # applied only when a checkpoint is restored onto a mesh.
    replicated = NamedSharding(mesh, PartitionSpec())
    actor_params = jax.device_put(model.init(k_actor), replicated)
    ref_params = jax.tree.map(jnp.copy, actor_params)  # frozen reference

    ctx = WorkerContext(
        mesh=mesh,
        rl=rl,
        engines=_build_engines(model, cfg, rl, tok, spec, rollout,
                               env_runtime),
        dataloader=DistributedDataloader(
            dataset or SyntheticMathDataset(4096, seed=seed),
            mesh=mesh,
            global_batch=prompts_per_iter,
            seed=seed,
            prefetch=coordinator.prefetch,
        ),
        actor_state=jax.device_put(trainer.init_state(actor_params),
                                   replicated),
        ref_params=ref_params,
        tokenizer=tok,
        key=k_run,
        algorithm=spec,
    )
    if spec.uses_critic:
        ctx.critic_state = jax.device_put(
            trainer.init_state(critic_mod.init(cfg, k_critic)), replicated)
    ctx.env = env_runtime

    if distributed is not None and distributed.enabled:
        # Fleet DP gradient exchange: split the fused actor step so the
        # gradient crosses the host data plane between grad and apply —
        # bitwise-equivalent to the fused step when grad_compression="none"
        # (tests/test_fleet.py), genuinely int8 on the wire otherwise.
        from repro.distributed import fleet as fleet_mod

        fleet_ctx = fleet_mod.ensure_context(distributed)
        exchange = fleet_mod.GradExchange(
            fleet_ctx, distributed.grad_compression
        )
        ctx.engines["actor_step"] = fleet_mod.fleet_actor_step(
            jax.jit(trainer.make_actor_grad_fn(model, rl, algorithm=spec)),
            jax.jit(trainer.make_actor_apply_fn(rl)),
            exchange,
        )
        ctx.fleet = fleet_ctx
        ctx.grad_exchange = exchange

    if obs is not None and obs.enabled:
        # Telemetry runtime: a process-global tracer (instrumented call
        # sites reach it via obs.get_tracer) plus a registry that absorbs
        # each iteration's metrics dict. Disabled obs leaves the global
        # tracer untouched — the zero-overhead default path.
        from repro import obs as obs_mod

        tracer = obs_mod.Tracer(
            enabled=obs.trace,
            host=distributed.process_id if distributed is not None else 0,
            capacity=obs.ring_capacity,
        )
        obs_mod.set_tracer(tracer)
        ctx.obs = obs_mod.ObsState(
            cfg=obs, tracer=tracer, registry=obs_mod.MetricsRegistry()
        )

    dag = dag or spec.dag_factory()
    if env_runtime is not None:
        # retarget the reward node at the environment stage (the env writes
        # the same `rewards` buffer key; validate_dag treats ENV as REWARD)
        dag = envs_mod.with_env_stage(dag)
    spec.validate_dag(dag)
    plan = DAGPlanner().plan(dag)
    if centralized:
        buffer_cls = CentralizedDatabuffer
    elif coordinator.double_buffer:
        buffer_cls = DoubleBufferedDatabuffer
    else:
        buffer_cls = DistributedDatabuffer
    buffer = buffer_cls(mesh)
    if async_pipeline is not None and async_pipeline.enabled:
        if centralized:
            raise ValueError(
                "the centralized baseline gathers every stage output through "
                "one controller and is inherently synchronous; async_pipeline "
                "cannot be combined with centralized=True"
            )
        from repro.core.async_worker import AsyncDAGWorker

        worker = AsyncDAGWorker(ctx, plan, registry or default_registry(),
                                buffer, coordinator,
                                async_cfg=async_pipeline)
    else:
        worker = DAGWorker(ctx, plan, registry or default_registry(), buffer,
                           coordinator)
    return Pipeline(worker=worker, ctx=ctx, buffer=buffer, dag=dag, plan=plan)
