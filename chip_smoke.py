#!/usr/bin/env python3
"""Bring-up smoke test: the main path on one TPU chip, at qwen2.5-7b widths.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: phase (c) on a 4-chip
                                      # mesh and on one chip, compared

Phases, in order, in this one process (a chip belongs to one process):
  (a) preconditions — a TPU backend, REPRO_KERNEL_MODE unset, and kernel
      dispatch resolving to compiled Pallas;
  (b) every main-path kernel at real widths against its ``kernels/ref.py``
      oracle (float32, highest matmul precision);
  (c) three GRPO iterations through ``repro.launch.train.main`` with the
      continuous rollout engine, on qwen2.5-7b at its published widths with
      depth and vocabulary cut (printed below);
  (d) request streaming through ``repro.launch.serve.run_streaming``.

Any failed check raises, so the exit code is non-zero and no result line is
printed. The last line of a passing run is one JSON object naming the
device. Weights are random, made from a fixed seed. Times printed are smoke
timings (first calls include compilation), not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops, ref  # noqa: E402
from repro.utils.jax_compat import use_mesh  # noqa: E402

SEED = 0
# qwen2.5-7b published widths; depth and vocabulary cut to fit one 16 GB
# chip next to fp32 Adam state (see smoke_model)
SMOKE_LAYERS, SMOKE_VOCAB = 2, 19_008
KERNEL_MARKER = "tpu_custom_call"


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes() -> list:
    """``peak_bytes_in_use`` of every local device (None where the backend
    keeps no such statistic)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[smoke] FAILED: {what}")
    log(f"[smoke] ok: {what}")


# --------------------------------------------------------------------------- #
# (a) preconditions
# --------------------------------------------------------------------------- #
def preconditions(chips: int) -> dict:
    from repro.utils.compile_cache import enable_compile_cache

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"JAX backend is a TPU (found {devs[0].platform!r})")
    check("REPRO_KERNEL_MODE" not in os.environ,
          "REPRO_KERNEL_MODE is unset")
    check(ops.current_mode() == "pallas",
          f"kernel dispatch resolves to compiled Pallas "
          f"(got {ops.current_mode()!r})")
    check(len(devs) >= chips, f"{chips} chip(s) present (found {len(devs)})")
    cache = enable_compile_cache()
    log(f"[smoke] device_kind={devs[0].device_kind!r} count={len(devs)} "
        f"jax={jax.__version__} compile_cache={cache}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# --------------------------------------------------------------------------- #
# (b) kernels at real widths vs their reference oracles
# --------------------------------------------------------------------------- #
def _close(name, got, want, *, atol, rtol, why):
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    err = float(np.max(np.abs(got - want) - rtol * np.abs(want)))
    check(np.all(np.isfinite(got)) and err <= atol,
          f"{name}: max(|d| - {rtol}*|ref|) = {err:.3g} <= {atol} ({why})")


def check_kernels(*, batch=8, seq=2048, heads=32, kv_heads=4, head_dim=128,
                  d_model=3584, vocab=19_008, page=16) -> None:
    """Every main-path kernel through ``ops`` (compiled Pallas on the chip)
    against its oracle evaluated in float32 at highest matmul precision on
    the same bf16/int8 inputs."""
    from repro.configs.base import pad_to, VOCAB_ALIGN
    from repro.models.lm import quant_kv

    bf16 = jnp.bfloat16
    ks = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))
    rnd = lambda shape: jax.random.normal(next(ks), shape, jnp.float32)
    f32 = lambda *xs: [x.astype(jnp.float32) for x in xs]
    # bf16 inputs, bf16 probabilities into the PV matmul and a bf16 output:
    # 8-bit mantissas bound each element near 2^-8 of its scale
    BF16_WHY = "bf16 operands and output, f32 accumulation"

    t0 = time.perf_counter()
    q = rnd((1, seq, heads, head_dim)).astype(bf16)
    k = rnd((1, seq, kv_heads, head_dim)).astype(bf16)
    v = rnd((1, seq, kv_heads, head_dim)).astype(bf16)
    got = jax.jit(lambda *a: ops.flash_attention(*a, causal=True))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = ref.flash_attention(*f32(q, k, v), causal=True)
    _close("flash_attention causal GQA "
           f"{heads}/{kv_heads} D={head_dim} S={seq}", got, want,
           atol=2e-2, rtol=2e-2, why=BF16_WHY)

    qd = rnd((batch, heads, head_dim)).astype(bf16)
    kc = rnd((batch, seq, kv_heads, head_dim)).astype(bf16)
    vc = rnd((batch, seq, kv_heads, head_dim)).astype(bf16)
    full = jnp.full((batch,), seq, jnp.int32)
    ragged = jnp.asarray(np.linspace(1, seq, batch).astype(np.int32))
    dec = jax.jit(ops.decode_attention)
    for name, lens in (("dense", full), ("ragged", ragged)):
        o, lse = dec(qd, kc, vc, lens)
        with jax.default_matmul_precision("highest"):
            o_r, lse_r = ref.decode_attention(*f32(qd, kc, vc), lens,
                                              return_lse=True)
        _close(f"decode_attention {name}", o, o_r, atol=2e-2, rtol=2e-2,
               why=BF16_WHY)
        _close(f"decode_attention {name} lse", lse, lse_r, atol=2e-2,
               rtol=1e-3, why="f32 log-sum-exp of bf16-product logits")

    kq, ksc = quant_kv(kc)
    vq, vsc = quant_kv(vc)
    o, _ = jax.jit(ops.decode_attention_quant)(qd, kq, vq, ksc, vsc, ragged)
    with jax.default_matmul_precision("highest"):
        o_r, _ = ref.decode_attention(
            qd.astype(jnp.float32), kq * ksc[..., None], vq * vsc[..., None],
            ragged, return_lse=True)
    _close("decode_attention_quant int8 ragged", o, o_r, atol=2e-2,
           rtol=2e-2, why="in-kernel dequantization in f32, bf16 output")

    pages_per_seq = seq // page
    n_pages = batch * pages_per_seq
    perm = np.random.default_rng(SEED).permutation(n_pages)
    tables = jnp.asarray(perm.reshape(batch, pages_per_seq).astype(np.int32))
    pool_k = jnp.zeros((n_pages, page, kv_heads, head_dim), bf16).at[perm].set(
        kc.reshape(n_pages, page, kv_heads, head_dim))
    pool_v = jnp.zeros((n_pages, page, kv_heads, head_dim), bf16).at[perm].set(
        vc.reshape(n_pages, page, kv_heads, head_dim))
    o, _ = jax.jit(ops.paged_decode_attention)(qd, pool_k, pool_v, tables,
                                               ragged)
    with jax.default_matmul_precision("highest"):
        o_r = ref.decode_attention(*f32(qd, kc, vc), ragged)
    _close("paged_decode_attention (scrambled block tables)", o, o_r,
           atol=2e-2, rtol=2e-2, why=BF16_WHY)

    vpad = pad_to(vocab, VOCAB_ALIGN)
    h = rnd((batch, d_model)).astype(bf16)
    w = (rnd((d_model, vpad)) / math.sqrt(d_model)).astype(bf16)
    with jax.default_matmul_precision("highest"):
        logits = ref._mask_vocab(h.astype(jnp.float32) @ w.astype(jnp.float32),
                                 vocab)
    logp = jax.nn.log_softmax(logits, axis=-1)
    sample = jax.jit(ops.fused_sample,
                     static_argnames=("temperature", "vocab_size"))
    key = jax.random.PRNGKey(SEED + 1)
    tok, lp = sample(h, w, key, temperature=1.0, vocab_size=vocab)
    rows = jnp.arange(batch)
    check(bool(jnp.all(tok < vocab)), "fused_sample never draws padded vocab")
    _close("fused_sample logprob of the drawn token", lp, logp[rows, tok],
           atol=1e-3, rtol=1e-4, why="f32 online log-sum-exp over 3584-wide "
           "bf16 dot products")
    tok0, _ = sample(h, w, key, temperature=0.0, vocab_size=vocab)
    gap = logits.max(axis=-1) - logits[rows, tok0]
    # argmax agrees up to accumulation-order near-ties
    check(bool(jnp.all(gap <= 1e-3)),
          f"fused_sample temperature 0 is the argmax (largest logit gap "
          f"{float(gap.max()):.3g} <= 1e-3)")

    x = rnd((batch * 256, d_model)).astype(bf16)
    wn = rnd((d_model,)) * 0.1
    got = jax.jit(ops.rmsnorm)(x, wn)
    want = ref.rmsnorm(x.astype(jnp.float32), wn)
    _close("rmsnorm", got, want, atol=2e-2, rtol=1e-2,
           why="f32 statistics, bf16 output")
    log(f"[smoke] kernels phase {time.perf_counter() - t0:.1f}s (smoke "
        f"timing, compiles included)")


# --------------------------------------------------------------------------- #
# (c) the RL iteration through the training driver
# --------------------------------------------------------------------------- #
def smoke_model():
    """qwen2.5-7b at its published widths (d_model 3584, 28 query heads
    padded to 32, 4 KV heads of 128, d_ff 18,944), cut to 2 layers and an
    eighth of the vocabulary. The pipeline keeps bf16 weights and grads,
    fp32 Adam m/v and a bf16 reference copy — about 14 bytes per parameter;
    the full vocabulary's untied embedding and head alone would be 1.09 B
    parameters (~15 GB), more than the chip holds."""
    from repro.configs import get_config

    return dataclasses.replace(get_config("qwen2.5-7b"),
                               num_layers=SMOKE_LAYERS, vocab_size=SMOKE_VOCAB)


def smoke_experiment(cfg, *, prompts=4, group=4, max_new=32,
                     mesh_shape=None):
    from repro.api import ExperimentSpec
    from repro.configs import RolloutEngineConfig
    from repro.rl import RLConfig

    return ExperimentSpec(
        model=cfg,
        # random weights earn no reward, so every GRPO advantage is zero;
        # the entropy bonus keeps a gradient flowing through the backward
        rl=RLConfig(algorithm="grpo", group_size=group,
                    max_new_tokens=max_new, lr=1e-5, entropy_coef=1e-3),
        rollout=RolloutEngineConfig(engine="continuous"),
        prompts_per_iter=prompts,
        mesh_shape=mesh_shape,
        seed=SEED,
    )


def run_rl(exp, iters: int = 3):
    """``repro.launch.train.main`` on ``exp``; returns (pipeline, history)."""
    from repro.launch import train

    with tempfile.TemporaryDirectory(dir=ROOT) as d:
        path = os.path.join(d, "experiment.json")
        with open(path, "w") as f:
            f.write(exp.to_json())
        t0 = time.perf_counter()
        pipe, history = train.main(["--experiment", path,
                                    "--iters", str(iters)])
    log(f"[smoke] {iters} RL iterations {time.perf_counter() - t0:.1f}s "
        f"(smoke timing, compiles included); peak_bytes_in_use per device "
        f"{peak_bytes()}")
    return pipe, history


def rollout_batch(pipe):
    """One more GENERATE through the pipeline's own engine: a real batch to
    lower the train step with and to score log-probs on. Like every call on
    the pipeline's arrays, it runs under the pipeline's mesh: on several
    chips that is what routes each kernel through shard_map."""
    ctx = pipe.ctx
    prompts, _ = ctx.prompt_source.next_prompts()
    with use_mesh(ctx.mesh):
        res = ctx.engines["generate"](ctx.actor_state.params, prompts,
                                      jax.random.PRNGKey(SEED + 2))
    return prompts, res


def check_rl(pipe, history, *, kernel_marker=KERNEL_MARKER):
    losses = [m["actor/loss"] for m in history]
    log(f"[smoke] actor losses {losses}")
    check(len(losses) == 3 and all(math.isfinite(x) for x in losses),
          "3 GRPO iterations, every actor loss finite")
    ctx = pipe.ctx
    moved = jax.tree.leaves(jax.tree.map(
        lambda a, b: jnp.any(a != b), ctx.actor_state.params, ctx.ref_params))
    check(any(bool(x) for x in moved),
          "actor parameters moved away from the frozen reference copy")

    prompts, res = rollout_batch(pipe)
    params = ctx.actor_state.params
    m = pipe_model(pipe)
    if kernel_marker:
        n, lp = prompts.shape
        smax = lp + ctx.rl.max_new_tokens
        caches = jax.eval_shape(lambda: m.init_caches(n, smax))
        with use_mesh(ctx.mesh):
            lowered = {
                "prefill": jax.jit(
                    lambda p, t: m.prefill(p, t, smax=smax)).lower(
                    params, prompts),
                "decode step": jax.jit(
                    lambda p, t, c, n_, k: m.decode_step_sample(
                        p, t, c, n_, k, ctx.rl.temperature)).lower(
                    params, prompts[:, -1], caches,
                    jnp.full((n,), lp, jnp.int32), jax.random.PRNGKey(0)),
                "train step": ctx.engines["actor_step"].lower(
                    ctx.actor_state, train_batch(res)),
            }
        for name, low in lowered.items():
            check(kernel_marker in low.as_text(),
                  f"lowered {name} contains {kernel_marker}")

    lp_kernel = logprobs(pipe, params, res.tokens)
    ops.set_mode("ref")
    try:
        lp_ref = logprobs(pipe, params, res.tokens)
    finally:
        ops.set_mode(None)
    mask = np.asarray(res.response_mask, bool)
    diff = np.abs(np.asarray(lp_kernel) - np.asarray(lp_ref))[mask]
    log(f"[smoke] response log-probs kernel vs ref: max |d| "
        f"{float(diff.max()):.4g}, mean |d| {float(diff.mean()):.4g} over "
        f"{int(mask.sum())} tokens")
    # bf16 activations through two 3584-wide blocks and a 3584-wide head:
    # logits move by ~1e-2, so each log-prob by a few 1e-2 at most
    check(float(diff.max()) <= 0.1,
          "Pallas-forward log-probs match the ref forward within 0.1 nats")


def pipe_model(pipe):
    return pipe.ctx.engines["generate"].model


def logprobs(pipe, params, tokens):
    """Per-token log-probs under a fresh jit (so the kernel mode in force now
    is the one traced) and the pipeline's mesh."""
    model = pipe_model(pipe)
    with use_mesh(pipe.ctx.mesh):
        return np.asarray(jax.jit(lambda p, t: model.logprobs(p, t)[0])(
            params, np.asarray(tokens)))


def train_batch(res):
    """The actor-step batch the GRPO DAG assembles from one rollout."""
    return {
        "tokens": res.tokens,
        "response_mask": res.response_mask,
        "old_logprob": res.old_logprob,
        "advantages": jnp.zeros_like(res.old_logprob),
        "ref_logprob": res.old_logprob,
    }


# --------------------------------------------------------------------------- #
# (d) request streaming through the serving driver
# --------------------------------------------------------------------------- #
def run_serving(model, params, *, num_requests=8, max_len=256, max_new=32,
                slots=4) -> None:
    from repro.launch import serve

    args = serve.parse_args([
        "--num-requests", str(num_requests), "--max-len", str(max_len),
        "--max-new", str(max_new), "--slots", str(slots), "--no-realtime",
        "--seed", str(SEED)])
    t0 = time.perf_counter()
    streams = serve.run_streaming(model, params, args)  # exits if unfinished
    check(len(streams) == num_requests
          and all(s.finished and len(s.tokens) > 0 for s in streams),
          f"all {num_requests} served streams finished with tokens")
    log(f"[smoke] serving phase {time.perf_counter() - t0:.1f}s (smoke "
        f"timing, warm-up compiles included); peak_bytes_in_use per device "
        f"{peak_bytes()}")


# --------------------------------------------------------------------------- #
def one_chip() -> None:
    check_kernels()
    cfg = smoke_model()
    log(f"[smoke] model: {cfg.name} widths d_model={cfg.d_model} "
        f"heads={cfg.num_heads}->{cfg.padded_heads} kv_heads="
        f"{cfg.num_kv_heads} head_dim={cfg.head_dim} d_ff={cfg.d_ff}; cut: "
        f"num_layers 28->{cfg.num_layers}, vocab 152064->{cfg.vocab_size} "
        f"({cfg.num_params() / 1e9:.3f} B params)")
    pipe, history = run_rl(smoke_experiment(cfg))
    check_rl(pipe, history)
    model, params = pipe_model(pipe), pipe.ctx.actor_state.params
    del pipe, history  # free the optimizer state and reference copy
    run_serving(model, params)


def four_chips() -> None:
    """Phase (c) on the 4-chip data-parallel mesh, then on one chip with the
    same seed: the first iteration's loss and the initial policy's log-probs
    of one batch must agree. Each pipeline is freed before the next is built
    (both together would not fit device 0)."""
    cfg = smoke_model()
    first_loss, lps, tokens, mask = {}, {}, None, None
    for name, shape in (("4-chip mesh", None), ("1 chip", (1, 1))):
        pipe, history = run_rl(smoke_experiment(cfg, mesh_shape=shape))
        leaf = jax.tree.leaves(pipe.ctx.actor_state.opt.m)[0]
        log(f"[smoke] {name}: mesh {dict(pipe.ctx.mesh.shape)}; Adam state "
            f"{leaf.sharding.spec} over {len(leaf.devices())} device(s)")
        check(all(math.isfinite(m["actor/loss"]) for m in history),
              f"{name}: every actor loss finite")
        first_loss[name] = history[0]["actor/loss"]
        if tokens is None:
            _, res = rollout_batch(pipe)
            tokens = np.asarray(res.tokens)
            mask = np.asarray(res.response_mask, bool)
        lps[name] = logprobs(pipe, pipe.ctx.ref_params, tokens)
        del pipe, history
    log(f"[smoke] first-iteration actor loss {first_loss}")
    d_loss = abs(first_loss["4-chip mesh"] - first_loss["1 chip"])
    check(d_loss <= 1e-2, f"first-iteration loss agrees (|d| = {d_loss:.3g} "
          "<= 1e-2: same seed and data, bf16 reduction order differs)")
    diff = float(np.max(np.abs(lps["4-chip mesh"] - lps["1 chip"])[mask]))
    check(diff <= 5e-2, f"initial-policy log-probs of one batch agree across "
          f"meshes (max |d| = {diff:.3g} <= 5e-2 nats, bf16 activations)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    device = preconditions(args.chips)
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
