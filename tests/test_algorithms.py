"""Algorithm-plugin API tests: AlgorithmSpec registry, ExperimentSpec facade,
and the redesign's equivalence contract — the spec-driven grpo/ppo paths must
be bitwise-identical to the pre-redesign string-dispatch code (whose exact
formulas are inlined here as the reference)."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec
from repro.configs import ARCHS, DataCoordinatorConfig, reduced
from repro.core import DAG, Node, NodeType, Role, build_pipeline
from repro.core.dag import DAGError
from repro.models import get_model
from repro.rl import (
    AlgorithmSpec,
    RLConfig,
    get_algorithm,
    list_algorithms,
    register_algorithm,
)
from repro.rl import advantage as adv_mod
from repro.rl import loss as losses
from repro.rl import trainer
from repro.rl.algorithms import critic_free_dag, grpo_dag, ppo_dag


def small_cfg(**kw):
    base = dict(vocab_size=260, num_layers=2, d_model=64, num_heads=4,
                num_kv_heads=2, head_dim=16, d_ff=128)
    base.update(kw)
    return reduced(ARCHS["qwen2.5-7b"], **base)


# --------------------------------------------------------------------------- #
# registry
# --------------------------------------------------------------------------- #
def test_builtin_algorithms_registered():
    assert {"grpo", "ppo", "rloo", "reinforce_pp"} <= set(list_algorithms())
    assert get_algorithm("ppo").uses_critic
    assert not get_algorithm("grpo").uses_critic
    assert get_algorithm("grpo").group_size(RLConfig(group_size=8)) == 8
    assert get_algorithm("ppo").group_size(RLConfig(group_size=8)) == 1


def test_unknown_algorithm_lists_registered_and_nearest():
    with pytest.raises(KeyError) as ei:
        get_algorithm("gropo")
    msg = str(ei.value)
    assert "grpo" in msg and "Registered" in msg


def test_duplicate_registration_requires_override():
    spec = get_algorithm("grpo")
    with pytest.raises(KeyError):
        register_algorithm(spec)
    assert register_algorithm(spec, override=True) is spec


# --------------------------------------------------------------------------- #
# equivalence contract: spec callables == pre-redesign inline branches
# --------------------------------------------------------------------------- #
def _fake_batch(key, B=8, T=12, prompt=5):
    ks = jax.random.split(key, 4)
    lp = -jnp.abs(jax.random.normal(ks[0], (B, T)))
    mask = jnp.concatenate(
        [jnp.zeros((B, prompt), bool), jnp.ones((B, T - prompt), bool)], 1)
    return {
        "old_logprob": lp * mask,
        "ref_logprob": (lp + 0.1 * jax.random.normal(ks[1], (B, T))) * mask,
        "advantages": jax.random.normal(ks[2], (B, T)) * mask,
        "response_mask": mask,
        "old_values": jax.random.normal(ks[3], (B, T)) * mask,
    }


def test_grpo_actor_loss_bitwise_matches_pre_redesign():
    rl = RLConfig(algorithm="grpo", clip_eps=0.2, kl_coef=0.003)
    batch = _fake_batch(jax.random.PRNGKey(0))
    logprob = batch["old_logprob"] + 0.05
    # pre-redesign: trainer.actor_loss_fn's `if rl.algorithm == "grpo"` arm
    want = losses.grpo_loss(
        logprob, batch["old_logprob"], batch["ref_logprob"],
        batch["advantages"], batch["response_mask"],
        clip_eps=rl.clip_eps, kl_coef=rl.kl_coef)
    got = get_algorithm("grpo").actor_loss(rl, logprob, batch)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


def test_ppo_actor_loss_bitwise_matches_pre_redesign():
    rl = RLConfig(algorithm="ppo", clip_eps=0.2)
    batch = _fake_batch(jax.random.PRNGKey(1))
    logprob = batch["old_logprob"] - 0.03
    # pre-redesign: the `else` arm
    want = losses.ppo_policy_loss(
        logprob, batch["old_logprob"], batch["advantages"],
        batch["response_mask"], clip_eps=rl.clip_eps)
    got = get_algorithm("ppo").actor_loss(rl, logprob, batch)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


def test_grpo_advantage_engine_bitwise_matches_pre_redesign():
    rl = RLConfig(algorithm="grpo", group_size=4)
    rewards = jax.random.uniform(jax.random.PRNGKey(2), (8,))
    mask = jnp.ones((8, 6), bool)
    want = adv_mod.grpo(rewards, mask, group_size=rl.group_size)
    got = get_algorithm("grpo").make_advantage(rl)(rewards, mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ppo_advantage_engine_bitwise_matches_pre_redesign():
    rl = RLConfig(algorithm="ppo", kl_coef=0.002, gamma=0.99, gae_lambda=0.9)
    key = jax.random.PRNGKey(3)
    batch = _fake_batch(key)
    rewards = jax.random.uniform(key, (8,))
    mask, old_lp = batch["response_mask"], batch["old_logprob"]
    ref_lp, values = batch["ref_logprob"], batch["old_values"]

    # pre-redesign: _ppo_adv as it appeared inline in pipeline._build_engines
    B, T = mask.shape
    kl = old_lp - ref_lp
    m = mask.astype(jnp.float32)
    last = jnp.maximum(jnp.sum(m, axis=1) - 1, 0).astype(jnp.int32)
    first = jnp.argmax(mask, axis=1)
    pos = jnp.clip(first + last, 0, T - 1)
    tok_rewards = -rl.kl_coef * kl * m
    tok_rewards = tok_rewards.at[jnp.arange(B), pos].add(rewards)
    want_adv, want_ret = adv_mod.gae(
        tok_rewards, values * m, m, gamma=rl.gamma, lam=rl.gae_lambda)
    want_adv = adv_mod.whiten(want_adv, m)

    got_adv, got_ret = get_algorithm("ppo").make_advantage(rl)(
        rewards, mask, old_lp, ref_lp, values)
    np.testing.assert_array_equal(np.asarray(got_adv), np.asarray(want_adv))
    np.testing.assert_array_equal(np.asarray(got_ret), np.asarray(want_ret))


@pytest.mark.parametrize("algo", ["grpo", "ppo"])
def test_experimentspec_compile_bitwise_matches_build_pipeline(algo):
    """The facade is a pure compiler: ExperimentSpec.compile() must reproduce
    a direct build_pipeline() run bitwise (same seeds, same engines)."""
    cfg = small_cfg()
    rl = RLConfig(algorithm=algo, group_size=4, max_new_tokens=4, lr=1e-4,
                  critic_lr=1e-4)
    h_direct = build_pipeline(cfg, rl, prompts_per_iter=4, seed=5).run(3)
    exp = ExperimentSpec(model=cfg, rl=rl, prompts_per_iter=4, seed=5)
    pipe = exp.compile()
    h_spec = pipe.run(3)
    for a, b in zip(h_direct, h_spec):
        for k in a:
            if k.startswith("time/"):
                continue
            assert a[k] == b[k], k  # exact, not approx


# --------------------------------------------------------------------------- #
# new algorithms: estimator math + end-to-end smoke
# --------------------------------------------------------------------------- #
def test_rloo_advantage_hand_calc():
    rewards = jnp.array([1.0, 0.0, 0.5, 0.5])  # two groups of 2
    mask = jnp.ones((4, 3))
    adv = adv_mod.rloo(rewards, mask, group_size=2)
    # leave-one-out baseline: group 0 -> [1-0, 0-1]; group 1 -> [0, 0]
    np.testing.assert_allclose(np.asarray(adv[:, 0]),
                               [1.0, -1.0, 0.0, 0.0], atol=1e-6)
    # group-mean of LOO advantages is zero
    assert abs(float(jnp.sum(adv[:2, 0]))) < 1e-6


def test_rloo_scales_grpo_centering():
    """RLOO advantages are the group-centered rewards scaled by G/(G-1)."""
    rewards = jax.random.uniform(jax.random.PRNGKey(0), (8,))
    mask = jnp.ones((8, 4))
    g = 4
    adv = adv_mod.rloo(rewards, mask, group_size=g)
    centered = rewards.reshape(2, g) - jnp.mean(rewards.reshape(2, g), 1,
                                                keepdims=True)
    want = (centered * g / (g - 1)).reshape(8)[:, None] * mask
    np.testing.assert_allclose(np.asarray(adv), np.asarray(want), atol=1e-6)


def test_reinforce_pp_advantage_is_global_batch_normalized():
    rewards = jnp.array([1.0, 0.0, 3.0, 0.0])
    mask = jnp.ones((4, 2))
    adv = adv_mod.reinforce_pp(rewards, mask)
    col = np.asarray(adv[:, 0])
    assert abs(col.mean()) < 1e-5
    np.testing.assert_allclose(col.std(), 1.0, atol=1e-3)
    # NOT per-group: two identical-reward pairs would all be 0 under grpo
    assert not np.allclose(col, 0.0)


@pytest.mark.parametrize("algo", ["rloo", "reinforce_pp"])
def test_new_algorithms_train_end_to_end(algo):
    """Acceptance: rloo and reinforce_pp train via ExperimentSpec.compile()."""
    exp = ExperimentSpec(
        model=small_cfg(),
        rl=RLConfig(algorithm=algo, group_size=4, max_new_tokens=4, lr=1e-3,
                    kl_coef=0.0),
        prompts_per_iter=4,
        seed=0,
    )
    pipe = exp.compile()
    spec = get_algorithm(algo)
    assert not spec.uses_critic
    assert "critic_step" not in pipe.ctx.engines
    hist = pipe.run(3)
    for m in hist:
        assert np.isfinite(m["actor/loss"])
        assert m["rollout/tokens"] > 0
    # grouped rollouts: 4 prompts x group 4, each 1 to max_new tokens
    assert 4 * 4 <= hist[-1]["rollout/tokens"] <= 4 * 4 * 4
    if algo == "reinforce_pp":
        assert "actor/kl" not in hist[-1]  # no reference model in the loss
        assert "reference_inference" not in pipe.plan.order


def test_custom_algorithm_registration_under_50_loc():
    """The docs' pluggability claim: a working custom algorithm (constant
    baseline REINFORCE) registers and trains without touching the core."""
    def make_adv(rl):
        return lambda rewards, mask: (
            (rewards - 0.5)[:, None] * mask.astype(jnp.float32))

    spec = AlgorithmSpec(
        name="reinforce_const",
        dag_factory=critic_free_dag,
        make_advantage=make_adv,
        actor_loss=get_algorithm("reinforce_pp").actor_loss,
        grouped_rollouts=True,
    )
    register_algorithm(spec, override=True)
    try:
        exp = ExperimentSpec(
            model=small_cfg(),
            rl=RLConfig(algorithm="reinforce_const", group_size=2,
                        max_new_tokens=4, lr=1e-3),
            prompts_per_iter=4,
        )
        m = exp.compile().run(2)[-1]
        assert np.isfinite(m["actor/loss"])
    finally:
        from repro.rl.algorithms import _ALGORITHMS

        _ALGORITHMS.pop("reinforce_const", None)


# --------------------------------------------------------------------------- #
# DAG validation errors
# --------------------------------------------------------------------------- #
def test_dag_cycle_raises():
    with pytest.raises(DAGError, match="cycle"):
        DAG.from_nodes([
            Node("a", Role.ACTOR, NodeType.COMPUTE, deps=("b",)),
            Node("b", Role.ACTOR, NodeType.COMPUTE, deps=("a",)),
        ])


def test_dag_unknown_dep_raises():
    with pytest.raises(DAGError, match="unknown dependency"):
        DAG.from_nodes([Node("a", Role.ACTOR, NodeType.COMPUTE,
                             deps=("nope",))])


def test_dag_duplicate_id_raises():
    with pytest.raises(DAGError, match="duplicate"):
        DAG.from_nodes([
            Node("a", Role.ACTOR, NodeType.COMPUTE),
            Node("a", Role.REWARD, NodeType.COMPUTE),
        ])


def test_missing_required_role_raises():
    """A PPO run on a critic-less DAG must fail fast with the missing roles."""
    with pytest.raises(DAGError, match="critic"):
        get_algorithm("ppo").validate_dag(grpo_dag())
    # and through the compile path
    exp = ExperimentSpec(
        model=small_cfg(),
        rl=RLConfig(algorithm="ppo", max_new_tokens=4),
        prompts_per_iter=4,
        dag=grpo_dag().to_spec(),
    )
    with pytest.raises(DAGError, match="required roles"):
        exp.compile()


def test_builtin_dags_satisfy_their_specs():
    for name in list_algorithms():
        spec = get_algorithm(name)
        spec.validate_dag(spec.dag_factory())


# --------------------------------------------------------------------------- #
# ExperimentSpec serialization
# --------------------------------------------------------------------------- #
def test_experimentspec_json_roundtrip():
    exp = ExperimentSpec(
        model=small_cfg(),
        rl=RLConfig(algorithm="rloo", group_size=4, lr=3e-5),
        coordinator=DataCoordinatorConfig(double_buffer=True, prefetch=2,
                                          load_balance=True),
        mesh_shape=(2, 4),
        mesh_axes=("data", "model"),
        prompts_per_iter=16,
        centralized=True,
        seed=42,
        dag=ppo_dag().to_spec(),
    )
    via_json = ExperimentSpec.from_json(exp.to_json())
    assert via_json == exp
    via_dict = ExperimentSpec.from_dict(
        json.loads(json.dumps(exp.to_dict())))
    assert via_dict == exp


def test_experimentspec_defaults_roundtrip():
    exp = ExperimentSpec(model=small_cfg())
    assert ExperimentSpec.from_json(exp.to_json()) == exp
    assert exp.algorithm.name == "grpo"


def test_experimentspec_compile_uses_embedded_dag():
    """The dag dict travels through JSON and drives the compiled plan."""
    custom = DAG.from_nodes([
        Node("actor_generation", Role.ACTOR, NodeType.GENERATE),
        Node("reward_compute", Role.REWARD, NodeType.COMPUTE,
             deps=("actor_generation",)),
        Node("advantage_compute", Role.ADVANTAGE, NodeType.COMPUTE,
             deps=("reward_compute",)),
        Node("actor_train", Role.ACTOR, NodeType.MODEL_TRAIN,
             deps=("advantage_compute",)),
    ])
    exp = ExperimentSpec(
        model=small_cfg(),
        rl=RLConfig(algorithm="grpo", group_size=2, max_new_tokens=4,
                    kl_coef=0.0),
        prompts_per_iter=2,
        dag=custom.to_spec(),
    )
    pipe = ExperimentSpec.from_json(exp.to_json()).compile()
    assert pipe.plan.order == ["actor_generation", "reward_compute",
                               "advantage_compute", "actor_train"]
    assert "reference_inference" not in pipe.plan.order
    m = pipe.run(1)[-1]
    assert np.isfinite(m["actor/loss"])


# --------------------------------------------------------------------------- #
# trainer-level spec threading
# --------------------------------------------------------------------------- #
def test_make_actor_step_accepts_explicit_spec():
    cfg = small_cfg()
    model = get_model(cfg)
    rl = RLConfig(algorithm="grpo", lr=1e-3, group_size=4)
    params = model.init(jax.random.PRNGKey(0))
    batch = _fake_batch(jax.random.PRNGKey(1), B=4, T=10)
    batch["tokens"] = jax.random.randint(jax.random.PRNGKey(2), (4, 10), 3, 250)
    s_named, m_named = jax.jit(trainer.make_actor_step(model, rl))(
        trainer.init_state(params), batch)
    s_spec, m_spec = jax.jit(
        trainer.make_actor_step(model, rl, algorithm=get_algorithm("grpo")))(
        trainer.init_state(params), batch)
    for a, b in zip(jax.tree.leaves(s_named.params), jax.tree.leaves(s_spec.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(m_named["loss"]) == float(m_spec["loss"])
