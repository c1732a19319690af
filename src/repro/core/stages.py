"""Built-in stage functions — the bodies behind the Fig. 5 dispatch table.

Stage shardings realize the paper's per-stage parallelism: model-bound stages
(generate / inference / train) shard the batch over the `data` axes only (the
`model` axis carries TP), while pure COMPUTE stages (reward, advantage) shard
the batch over *all* axes — a genuinely different DP size, so the
Distributed Databuffer's redistribution path (Figs. 7-8) is exercised at
every model<->compute boundary exactly as in the paper.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.dag import Node


def _algo(ctx):
    """The AlgorithmSpec driving this run (bound by build_pipeline; resolved
    from the registry for hand-rolled contexts)."""
    from repro.rl import algorithms

    return algorithms.resolve(ctx)


def _specs(ctx):
    """(model-stage batch spec, compute-stage batch spec) for ctx.mesh."""
    axes = ctx.mesh.axis_names
    data_axes = tuple(a for a in axes if a != "model")
    model_spec = P(data_axes)
    compute_spec = P(tuple(axes))
    return model_spec, compute_spec


# --------------------------------------------------------------------------- #
def actor_generate(ctx, buffer, node: Node) -> Dict:
    """(ACTOR, GENERATE): pull the iteration's prompts from the worker-bound
    prompt iterator (``ctx.prompt_source``, already group-expanded), drive
    the generation engine — the jitted lockstep path or the slot-refill
    continuous-batching engine, same call contract — and store the
    trajectory. Continuous-engine runs additionally report the engine's
    tokens/sec, padding-waste, and slot-occupancy metrics."""
    model_spec, _ = _specs(ctx)
    if ctx.prompt_source is None:
        # hand-rolled ctx without a worker: bind the same iterator the
        # worker would, so group expansion has exactly one implementation
        from repro.core.worker import PromptSource

        ctx.prompt_source = PromptSource(
            ctx.dataloader, _algo(ctx).group_size(ctx.rl))
    prompts, answers = ctx.prompt_source.next_prompts()
    key = ctx.next_key()
    engine = ctx.engines["generate"]
    res = engine(ctx.actor_state.params, prompts, key)
    buffer.put("tokens", res.tokens, model_spec)
    buffer.put("response_mask", res.response_mask, model_spec)
    buffer.put("old_logprob", res.old_logprob, model_spec)
    buffer.put("answers", answers, model_spec)
    if res.role_mask is not None:
        # multi-turn episodes: per-token roles (0 prompt, 1 action, 2 env
        # observation) so downstream masking can be audited; response_mask
        # already excludes observation tokens
        buffer.put("role_mask", res.role_mask, model_spec)
    env_out = getattr(engine, "last_env", None)
    if env_out:
        # engine-driven episodes: env rewards/turns ride the buffer to the
        # (ENV, COMPUTE) stage (they repack with the batch under the load
        # balancer exactly like every other per-sequence key)
        buffer.put("env_rewards", jnp.asarray(env_out["rewards"]), model_spec)
        buffer.put("env_turns", jnp.asarray(env_out["turns"]), model_spec)
    out = {
        "rollout/mean_len": float(jnp.mean(res.lengths.astype(jnp.float32))),
        "rollout/tokens": float(jnp.sum(res.lengths)),
    }
    stats = getattr(engine, "last_stats", None)
    if stats:  # continuous engine: slot/throughput accounting
        out.update({f"rollout/{k}": float(v) for k, v in stats.items()})
    return out


def actor_logprobs(ctx, buffer, node: Node) -> Dict:
    """(ACTOR, MODEL_INFERENCE): recompute behaviour logprobs under the
    training engine (verl does this because its rollout engine differs from
    its training engine; ours are exact, so this node is optional and used by
    custom DAGs to validate engine agreement)."""
    model_spec, _ = _specs(ctx)
    tokens = buffer.get("tokens", model_spec)
    lp, _ = ctx.engines["logprobs"](ctx.actor_state.params, tokens)
    buffer.put("old_logprob", lp * buffer.get("response_mask", model_spec), model_spec)
    return {}


def reference_logprobs(ctx, buffer, node: Node) -> Dict:
    model_spec, _ = _specs(ctx)
    tokens = buffer.get("tokens", model_spec)
    lp, _ = ctx.engines["logprobs"](ctx.ref_params, tokens)
    buffer.put("ref_logprob", lp, model_spec)
    return {}


def critic_values(ctx, buffer, node: Node) -> Dict:
    model_spec, _ = _specs(ctx)
    tokens = buffer.get("tokens", model_spec)
    v = ctx.engines["values"](ctx.critic_state.params, tokens)
    buffer.put("old_values", v, model_spec)
    return {}


def reward_compute(ctx, buffer, node: Node) -> Dict:
    """(REWARD, COMPUTE): function reward (paper's PPO uses a function reward
    in place of a reward model). Runs at compute-stage DP (all axes)."""
    _, compute_spec = _specs(ctx)
    tokens = buffer.get("tokens", compute_spec)
    mask = buffer.get("response_mask", compute_spec)
    answers = buffer.get("answers", P(compute_spec[0]))
    rewards = ctx.engines["reward"](tokens, mask, answers)
    buffer.put("rewards", rewards, P(compute_spec[0]))
    return {"reward/mean": float(jnp.mean(rewards))}


def env_compute(ctx, buffer, node: Node) -> Dict:
    """(ENV, COMPUTE): episode rewards from the environment subsystem
    (``repro.rl.envs``; replaces the REWARD stage when ``EnvConfig`` names an
    env). Engine-driven multi-turn runs already stepped the envs during
    generation — their rewards ride the buffer as ``env_rewards``; the
    lockstep engine's single-turn path steps each episode post-hoc over the
    finished rollout here."""
    _, compute_spec = _specs(ctx)
    seq_spec = P(compute_spec[0])
    out: Dict[str, float] = {}
    if "env_rewards" in buffer.keys():
        rewards = buffer.get("env_rewards", seq_spec)
        turns = buffer.get("env_turns", seq_spec)
        out["env/turns_mean"] = float(jnp.mean(turns.astype(jnp.float32)))
    else:
        if ctx.env is None:
            raise RuntimeError(
                "env_compute needs WorkerContext.env (an EnvRuntime); "
                "was the pipeline built with an enabled EnvConfig?"
            )
        tokens = buffer.get("tokens", compute_spec)
        mask = buffer.get("response_mask", compute_spec)
        rewards = jnp.asarray(ctx.env.score_single_turn(
            np.asarray(jax.device_get(tokens)),
            np.asarray(jax.device_get(mask))))
    buffer.put("rewards", rewards, seq_spec)
    out["reward/mean"] = float(jnp.mean(rewards))
    return out


def advantage_compute(ctx, buffer, node: Node) -> Dict:
    """(ADVANTAGE, COMPUTE): run the spec's advantage engine. The spec
    declares which extra buffer keys the engine consumes beyond
    (rewards, mask) — e.g. PPO's GAE reads logprobs + values — and which
    keys its outputs land under (advantages, and returns for critic
    algorithms)."""
    spec = _algo(ctx)
    _, compute_spec = _specs(ctx)
    seq_spec = P(compute_spec[0])
    mask = buffer.get("response_mask", compute_spec)
    rewards = buffer.get("rewards", seq_spec)
    extra = [buffer.get(k, compute_spec) for k in spec.advantage_inputs]
    out = ctx.engines["advantage"](rewards, mask, *extra)
    if len(spec.advantage_outputs) == 1:
        out = (out,)
    for key, val in zip(spec.advantage_outputs, out):
        buffer.put(key, val, compute_spec)
    return {}


def actor_train(ctx, buffer, node: Node) -> Dict:
    model_spec, _ = _specs(ctx)
    batch = {
        "tokens": buffer.get("tokens", model_spec),
        "response_mask": buffer.get("response_mask", model_spec),
        "old_logprob": buffer.get("old_logprob", model_spec),
        "advantages": buffer.get("advantages", model_spec),
    }
    if _algo(ctx).needs_reference:
        if "ref_logprob" in buffer.keys():
            batch["ref_logprob"] = buffer.get("ref_logprob", model_spec)
        else:
            # reference-free DAG variant (custom_dag example): KL term is 0
            batch["ref_logprob"] = batch["old_logprob"]
    if "behavior_logprob" in buffer.keys():
        # stale batch from the async scheduler: gen-time logprobs ride along
        # for the decoupled truncated-IS correction (trainer.apply_is_correction)
        batch["behavior_logprob"] = buffer.get("behavior_logprob", model_spec)
    ctx.actor_state, metrics = ctx.engines["actor_step"](ctx.actor_state, batch)
    return {f"actor/{k}": float(v) for k, v in metrics.items()}


def critic_train(ctx, buffer, node: Node) -> Dict:
    model_spec, _ = _specs(ctx)
    batch = {
        "tokens": buffer.get("tokens", model_spec),
        "response_mask": buffer.get("response_mask", model_spec),
        "old_values": buffer.get("old_values", model_spec),
        "returns": buffer.get("returns", model_spec),
    }
    ctx.critic_state, metrics = ctx.engines["critic_step"](ctx.critic_state, batch)
    return {f"critic/{k}": float(v) for k, v in metrics.items()}
