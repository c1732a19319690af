"""Continuous-batching rollout engine tests: token-for-token lockstep
equivalence under a fixed slot schedule, slot refill, early-exit decode,
length bucketing / chunked prefill, and the pipeline/ExperimentSpec wiring."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec
from repro.configs import ARCHS, RolloutEngineConfig, reduced
from repro.core import build_pipeline
from repro.models import get_model
from repro.rl import RLConfig
from repro.rl.rollout import generate
from repro.rl.rollout_engine import (
    ContinuousRolloutEngine,
    PromptQueue,
    lockstep_waste,
)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced(ARCHS["qwen2.5-7b"], vocab_size=260, num_layers=2)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(B, Lp, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, Lp), 3, 200)


# --------------------------------------------------------------------------- #
# equivalence contract
# --------------------------------------------------------------------------- #
def test_token_identical_to_lockstep_fixed_schedule(tiny_model):
    """Under a fixed slot schedule (num_slots >= batch, single bucket) the
    engine consumes lockstep's exact key schedule and must produce the same
    tokens, masks, and lengths — the acceptance criterion of the engine."""
    cfg, model, params = tiny_model
    B, Lp, T = 8, 6, 12
    prompt = _prompts(B, Lp)
    key = jax.random.PRNGKey(6)
    # token 129 is sampled early in three of the eight rows under this key
    # at temperature 2.0 (lengths 12 12 9 2 12 12 12 1) -> varied lengths
    ref = generate(model, params, prompt, key, max_new=T, temperature=2.0,
                   eos_id=129, pad_id=0)
    eng = ContinuousRolloutEngine(model, max_new=T, temperature=2.0,
                                  eos_id=129, pad_id=0)
    got = eng(params, prompt, key)
    np.testing.assert_array_equal(np.asarray(got.tokens), np.asarray(ref.tokens))
    np.testing.assert_array_equal(
        np.asarray(got.response_mask), np.asarray(ref.response_mask))
    np.testing.assert_array_equal(
        np.asarray(got.lengths), np.asarray(ref.lengths))
    # behaviour logprobs agree up to float reassociation (the engine's
    # refill prefill compiles as its own executable)
    np.testing.assert_allclose(
        np.asarray(got.old_logprob), np.asarray(ref.old_logprob), atol=5e-3)
    assert not np.all(np.asarray(ref.lengths) == T), "want some early EOS"


def test_token_identical_with_budgets(tiny_model):
    """Per-sequence response budgets: lockstep and the engine implement the
    same cap semantics, token-for-token, under the fixed schedule."""
    cfg, model, params = tiny_model
    B, Lp, T = 8, 6, 10
    prompt = _prompts(B, Lp, seed=4)
    budgets = jnp.asarray([1, 3, 10, 5, 2, 10, 7, 4], jnp.int32)
    key = jax.random.PRNGKey(12)
    ref = generate(model, params, prompt, key, max_new=T, temperature=1.0,
                   pad_id=0, budgets=budgets)
    np.testing.assert_array_equal(np.asarray(ref.lengths),
                                  np.asarray(budgets))  # cap binds (no EOS)
    eng = ContinuousRolloutEngine(model, max_new=T, temperature=1.0, pad_id=0)
    got = eng(params, prompt, key, budgets=np.asarray(budgets))
    np.testing.assert_array_equal(np.asarray(got.tokens), np.asarray(ref.tokens))
    np.testing.assert_array_equal(
        np.asarray(got.lengths), np.asarray(ref.lengths))


def test_token_identical_greedy(tiny_model):
    cfg, model, params = tiny_model
    prompt = _prompts(4, 5, seed=2)
    ref = generate(model, params, prompt, jax.random.PRNGKey(3), max_new=6,
                   temperature=0.0)
    eng = ContinuousRolloutEngine(model, max_new=6, temperature=0.0)
    got = eng(params, prompt, jax.random.PRNGKey(99))  # key-free when greedy
    np.testing.assert_array_equal(np.asarray(got.tokens), np.asarray(ref.tokens))


def _swa_model(window=8):
    cfg = reduced(ARCHS["qwen2.5-7b"], vocab_size=260, num_layers=2,
                  sliding_window=window)
    model = get_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def test_token_identical_to_lockstep_swa_ring():
    """A sliding window of 8 under an 18-wide arena: each slot's ring wraps
    and every decode step writes its ring slot in place; the engine stays
    token-for-token with lockstep."""
    cfg, model, params = _swa_model()
    B, Lp, T = 8, 6, 12
    prompt = _prompts(B, Lp, seed=3)
    key = jax.random.PRNGKey(6)
    ref = generate(model, params, prompt, key, max_new=T, temperature=2.0,
                   eos_id=129, pad_id=0)
    eng = ContinuousRolloutEngine(model, max_new=T, temperature=2.0,
                                  eos_id=129, pad_id=0)
    got = eng(params, prompt, key)
    np.testing.assert_array_equal(np.asarray(got.tokens), np.asarray(ref.tokens))
    np.testing.assert_array_equal(
        np.asarray(got.lengths), np.asarray(ref.lengths))
    np.testing.assert_allclose(
        np.asarray(got.old_logprob), np.asarray(ref.old_logprob), atol=5e-3)


@pytest.mark.parametrize("window", [None, 8])
def test_multi_turn_continuation_resumes_from_the_arena(window):
    """Multi-turn episodes through a 3-slot pool: each continuation gathers
    its rows from the in-place arena, feeds the observation through the
    decode step and scatters the rows back. The assembled sequences agree
    with a full forward at every action position, windowed or not."""
    from repro.configs import EnvConfig
    from repro.data.tokenizer import ByteTokenizer
    from repro.rl import envs
    from repro.rl.reward import make_math_prompts

    tok = ByteTokenizer()
    cfg, model, params = _swa_model(window)
    prompts, _ = make_math_prompts(np.random.default_rng(5), 6, tok)
    env_cfg = EnvConfig(name="dialog", max_turns=3, obs_budget=8)
    runtime = envs.EnvRuntime(envs.get_env("dialog"), env_cfg, tok)
    eng = ContinuousRolloutEngine(
        model, max_new=8, temperature=2.0, eos_id=tok.eos_id, pad_id=0,
        num_slots=3, env=runtime, max_turns=3, turn_budget=4, obs_budget=8)
    got = eng(params, jnp.asarray(prompts), jax.random.PRNGKey(8))
    assert eng.last_stats["cont_refills"] >= 1
    lp, _ = model.logprobs(params, got.tokens)
    m = np.asarray(got.response_mask)
    assert m.sum() > 0
    np.testing.assert_allclose(
        np.asarray(got.old_logprob)[m], np.asarray(lp)[m], atol=5e-2)


# --------------------------------------------------------------------------- #
# slot refill / early exit
# --------------------------------------------------------------------------- #
def test_slot_refill_completes_all_sequences(tiny_model):
    """4 slots over 16 prompts: every sequence completes, outputs are
    teacher-forcing consistent, and the queue actually refilled."""
    cfg, model, params = tiny_model
    B, Lp, T = 16, 6, 12
    prompt = _prompts(B, Lp)
    eng = ContinuousRolloutEngine(model, max_new=T, temperature=2.0,
                                  eos_id=3, pad_id=0, num_slots=4)
    got = eng(params, prompt, jax.random.PRNGKey(7))
    lens = np.asarray(got.lengths)
    assert np.all(lens >= 1) and np.all(lens <= T)
    np.testing.assert_array_equal(
        np.asarray(got.tokens[:, :Lp]), np.asarray(prompt))
    lp, _ = model.logprobs(params, got.tokens)
    m = np.asarray(got.response_mask)
    np.testing.assert_allclose(
        np.asarray(got.old_logprob)[m], np.asarray(lp)[m], atol=5e-2)
    s = eng.last_stats
    assert s["refills"] > 1, "16 prompts over 4 slots must refill"
    assert s["num_slots"] == 4
    assert 0.0 < s["slot_occupancy"] <= 1.0
    assert s["tokens"] == float(lens.sum())


def test_early_exit_all_eos_at_step_0(tiny_model):
    """Zeroed params make logits constant -> argmax is token 0; with
    eos_id=0 every sequence finishes at its first sampled token, and the
    while_loop must exit without a single decode step (lockstep would still
    scan all max_new-1 steps)."""
    cfg, model, params = tiny_model
    zeroed = jax.tree.map(jnp.zeros_like, params)
    eng = ContinuousRolloutEngine(model, max_new=16, temperature=0.0,
                                  eos_id=0, pad_id=0)
    got = eng(zeroed, _prompts(4, 6), jax.random.PRNGKey(0))
    assert np.all(np.asarray(got.lengths) == 1)
    assert eng.last_stats["decode_steps"] == 0.0
    assert eng.last_stats["padding_waste"] == 0.0


def test_early_exit_beats_lockstep_schedule(tiny_model):
    """With natural early EOS the engine must run fewer decode steps than
    lockstep's unconditional max_new-1."""
    cfg, model, params = tiny_model
    T = 48
    eng = ContinuousRolloutEngine(model, max_new=T, temperature=2.0,
                                  eos_id=3, pad_id=0)
    got = eng(params, _prompts(8, 6), jax.random.PRNGKey(11))
    lens = np.asarray(got.lengths)
    # slot s runs lens[s]-1 decode steps (token 1 comes from prefill); the
    # while_loop exits at the slowest slot instead of scanning to T-1
    assert eng.last_stats["decode_steps"] == max(lens) - 1


def test_refill_regression_mixed_budgets_no_starvation(tiny_model):
    """Queue drained mid-refill under a mixed-length budget set: every
    sequence must complete at exactly its budget (no slot starvation when
    late refills race the early-exit), and the occupancy metric must stay
    consistent with the token accounting — active lane-steps equal the
    decode-produced tokens, i.e. occupancy * slots * steps == sum(len - 1)
    over all sequences (each sequence's first token comes from prefill)."""
    cfg, model, params = tiny_model
    B, Lp, T, S = 12, 6, 16, 4
    prompt = _prompts(B, Lp, seed=21)
    # mixed budgets: several 1-token bursts (immediate-done refills), some
    # mid-length, a few full-budget stragglers — the drain pattern that
    # exercises pop() on a shrinking queue while slots free in bursts
    budgets = np.array([1, 16, 2, 1, 7, 16, 3, 1, 5, 2, 16, 4], np.int32)
    eng = ContinuousRolloutEngine(model, max_new=T, temperature=1.0,
                                  pad_id=0, num_slots=S)
    got = eng(params, prompt, jax.random.PRNGKey(17), budgets=budgets)
    lens = np.asarray(got.lengths)
    # no starvation: every sequence ran to its cap (no EOS id configured)
    np.testing.assert_array_equal(lens, budgets)
    s = eng.last_stats
    assert s["refills"] >= 2, "12 prompts over 4 slots must refill"
    # occupancy consistency: active lane-steps == decode-produced tokens
    active_steps = s["slot_occupancy"] * s["num_slots"] * s["decode_steps"]
    assert active_steps == pytest.approx(int((budgets - 1).sum()))
    assert 0.0 < s["slot_occupancy"] <= 1.0


# --------------------------------------------------------------------------- #
# bucketing / chunked prefill
# --------------------------------------------------------------------------- #
def test_prompt_queue_buckets_and_fifo():
    pad = 0
    prompts = np.zeros((6, 8), np.int32)
    for i, n in enumerate([3, 8, 2, 8, 5, 1]):
        prompts[i, :n] = 7  # n true tokens, rest pad
    q = PromptQueue(prompts, pad_id=pad, bucket=4)
    assert len(q) == 6
    # buckets: ceil(len/4)*4 -> {4: [0,2,5], 8: [1,3,4]}
    np.testing.assert_array_equal(q.bucket_len, [4, 8, 4, 8, 8, 4])
    lb, idxs = q.pop(2)
    assert lb in (4, 8) and len(idxs) == 2
    assert idxs == sorted(idxs), "FIFO within a bucket preserves order"
    total = len(idxs)
    while len(q):
        _, got = q.pop(3)
        total += len(got)
    assert total == 6


def test_prompt_queue_single_bucket_is_lockstep_schedule():
    prompts = np.full((4, 6), 9, np.int32)
    q = PromptQueue(prompts, pad_id=0, bucket=0)
    lb, idxs = q.pop(4)
    assert lb == 6 and idxs == [0, 1, 2, 3]


def test_prompt_queue_no_fresh_starvation_under_cont_pressure():
    """Regression: continuations used to be served unconditionally first,
    so an env re-queueing one continuation per finished turn — i.e. refill
    pressure exactly matching the pop rate — deferred fresh prompts
    forever. The streak bound must serve a fresh bucket within
    STARVATION_LIMIT + 1 pops no matter how fast continuations re-arrive."""
    from repro.rl.rollout_engine import _Continuation

    prompts = np.full((4, 8), 7, np.int32)
    q = PromptQueue(prompts, pad_id=0, bucket=4)
    q.push(_Continuation(0, np.array([5, 6]), None, 8))
    served_fresh_at = None
    for i in range(2 * PromptQueue.STARVATION_LIMIT + 2):
        kind, _, items = q.pop_work(2)
        if kind == "prefill":
            served_fresh_at = i
            break
        # adversary: replace every popped continuation immediately
        for c in items:
            q.push(_Continuation(c.row, c.feed, None, c.cache_len))
    assert served_fresh_at is not None, "fresh prompts starved"
    assert served_fresh_at <= PromptQueue.STARVATION_LIMIT


def test_prompt_queue_small_bucket_not_deferred_indefinitely():
    """Regression for the other starvation mode: fullest-bucket-first let a
    small bucket's head wait out every larger bucket. With aging, the lone
    short prompt must be served within a bounded number of pops even while
    the big bucket still holds work; FIFO within each bucket throughout."""
    prompts = np.zeros((12, 16), np.int32)
    prompts[0, :2] = 7  # row 0: the lone 4-bucket prompt
    for i in range(1, 12):
        prompts[i, :14] = 7  # rows 1..11: one deep 16-bucket
    q = PromptQueue(prompts, pad_id=0, bucket=4)
    popped = []
    for i in range(12):
        if not len(q):
            break
        lb, idxs = q.pop(1)
        popped.extend(idxs)
        if 0 in idxs:
            break
    assert 0 in popped, "short-bucket prompt starved"
    # the big bucket won the first STARVATION_LIMIT pops (fullest-first),
    # then aging forced the short bucket through
    assert popped.index(0) <= PromptQueue.STARVATION_LIMIT
    big = [r for r in popped if r != 0]
    assert big == sorted(big), "FIFO within a bucket must be preserved"


def test_bucketed_prefill_trims_padding(tiny_model):
    """Variable-length prompts through length-bucketed prefill: every
    sequence completes in dataset order and the refill batches prefill
    fewer lane-tokens than the padded maximum would."""
    cfg, model, params = tiny_model
    B, Lp, T = 8, 12, 8
    rng = np.random.default_rng(0)
    prompts = np.zeros((B, Lp), np.int32)
    for i in range(B):
        n = int(rng.integers(2, Lp + 1))
        prompts[i, :n] = rng.integers(3, 200, n)
    eng = ContinuousRolloutEngine(
        model, max_new=T, temperature=2.0, eos_id=3, pad_id=0,
        num_slots=4, prefill_bucket=4,
    )
    got = eng(params, jnp.asarray(prompts), jax.random.PRNGKey(5))
    s = eng.last_stats
    assert s["prefill_lane_tokens"] < B * Lp, "bucketing must trim padding"
    assert s["prefill_true_tokens"] <= s["prefill_lane_tokens"]
    lens = np.asarray(got.lengths)
    assert np.all(lens >= 1) and np.all(lens <= T)
    np.testing.assert_array_equal(
        np.asarray(got.tokens[:, :Lp]), np.asarray(prompts))


def test_chunked_prefill_token_match(tiny_model):
    """Chunked prefill (single bucket, greedy) produces the same tokens as
    the whole-prompt engine — the chunk boundary only reassociates floats."""
    cfg, model, params = tiny_model
    prompt = _prompts(4, 8, seed=9)
    whole = ContinuousRolloutEngine(model, max_new=6, temperature=0.0)
    chunked = ContinuousRolloutEngine(model, max_new=6, temperature=0.0,
                                      prefill_chunk=4)
    assert chunked.prefill_chunk == 4
    r1 = whole(params, prompt, jax.random.PRNGKey(0))
    r2 = chunked(params, prompt, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(r1.tokens), np.asarray(r2.tokens))


def test_chunked_prefill_gated_for_ssm_and_quant():
    import dataclasses

    cfg = reduced(ARCHS["mamba2-2.7b"], vocab_size=260)
    eng = ContinuousRolloutEngine(get_model(cfg), max_new=4, prefill_chunk=2)
    assert eng.prefill_chunk == 0, "SSM archs fall back to whole-prompt"
    # int8 caches too: a chunk would attend its prefix's quantize->
    # dequantized K/V, diverging from whole-prompt prefill well beyond
    # float reassociation
    qcfg = dataclasses.replace(
        reduced(ARCHS["qwen2.5-7b"], vocab_size=260), kv_quant=True)
    eng = ContinuousRolloutEngine(get_model(qcfg), max_new=4, prefill_chunk=2)
    assert eng.prefill_chunk == 0, "kv_quant falls back to whole-prompt"


# --------------------------------------------------------------------------- #
# config / pipeline wiring
# --------------------------------------------------------------------------- #
def test_rollout_engine_config_validation():
    with pytest.raises(ValueError, match="lockstep"):
        RolloutEngineConfig(engine="vllm")
    with pytest.raises(ValueError, match="num_slots"):
        RolloutEngineConfig(num_slots=-1)
    assert RolloutEngineConfig().engine == "lockstep"


def test_experiment_spec_rollout_round_trip():
    exp = ExperimentSpec(
        model=reduced(ARCHS["qwen2.5-7b"], vocab_size=260),
        rl=RLConfig(algorithm="grpo", group_size=2, max_new_tokens=8),
        rollout=RolloutEngineConfig(engine="continuous", num_slots=4,
                                    prefill_bucket=2),
    )
    assert ExperimentSpec.from_json(exp.to_json()) == exp
    # back-compat: dicts without the rollout key default to lockstep
    d = exp.to_dict()
    del d["rollout"]
    assert ExperimentSpec.from_dict(d).rollout.engine == "lockstep"


def test_continuous_engine_through_pipeline():
    """GENERATE stage drives the engine: full iterations run, slot metrics
    surface as rollout/*, and training consumes the trajectories."""
    cfg = reduced(ARCHS["qwen2.5-7b"], vocab_size=260)
    rl = RLConfig(algorithm="grpo", group_size=2, max_new_tokens=8, lr=1e-4)
    pipe = build_pipeline(
        cfg, rl, prompts_per_iter=4,
        rollout=RolloutEngineConfig(engine="continuous", num_slots=4),
    )
    hist = pipe.run(2)
    for m in hist:
        assert m["rollout/tokens"] > 0
        assert 0.0 < m["rollout/slot_occupancy"] <= 1.0
        assert 0.0 <= m["rollout/padding_waste"] < 1.0
        assert m["rollout/num_slots"] == 4
        assert any(k.startswith("actor/") for k in m)


def test_prompt_source_handoff():
    """The worker hands the GENERATE stage its prompt iterator: the bound
    PromptSource group-expands, and a swapped source is what the stage
    consumes."""
    cfg = reduced(ARCHS["qwen2.5-7b"], vocab_size=260)
    rl = RLConfig(algorithm="grpo", group_size=3, max_new_tokens=4)
    pipe = build_pipeline(cfg, rl, prompts_per_iter=2)
    assert pipe.ctx.prompt_source is not None
    assert pipe.ctx.prompt_source.group_size == 3
    prompts, answers = pipe.ctx.prompt_source.next_prompts()
    assert prompts.shape[0] == 6 and answers.shape[0] == 6  # 2 prompts x 3


def test_lockstep_waste_helper():
    assert lockstep_waste(np.array([8, 8]), 8) == 0.0
    # 2 sequences, lengths 1 and 8, max_new 8: decode produced 7 of 14 slots
    assert lockstep_waste(np.array([1, 8]), 8) == pytest.approx(0.5)
