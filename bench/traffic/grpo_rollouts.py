"""General generator of GRPO rollout traffic, driven by a mix file.

A mix file ``bench/traffic/<name>.json`` names this generator and gives:

- ``group_size``: rollouts per prompt;
- ``prompt_len``: ``{"lo", "hi"}``, true prompt lengths drawn log-uniform;
- ``prompt_width``: the padded prompt width every batch is cut to;
- ``max_new``: the response cap (``RLConfig.max_new_tokens``);
- ``budgets``: ``[[share, lo, hi], ...]``, per-sequence response budgets,
  uniform on ``[lo, hi]`` within each part of the mixture. A budget frees its
  sequence's slot exactly as an EOS would; random weights almost never
  sample EOS, so the budgets stand in for the response-length distribution.

Every seed gets the same sizes in another order. Iteration ``i`` draws its
lengths at stratified quantiles ``(j + v_i) / n`` of the stated
distributions, with ``v_i`` the van der Corput sequence, so any ``2**k``
consecutive iterations cover the mixture evenly and the amount of work in a
window does not depend on the seed. The seed permutes the sizes among the
rows and draws the token ids and answers.

The harness reads two functions of a generator: ``batches``, the stream of
iterations, and ``cycle``, the number of iterations a measured window
holds a whole multiple of.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

PAD = 0  # the program's byte tokenizer: PAD 0, BOS 1, EOS 2
FIRST_ID = 3  # prompt ids are drawn from [FIRST_ID, vocab_size)


@dataclasses.dataclass(frozen=True)
class Batch:
    """One iteration's traffic. ``prompts`` (P, prompt_width) right-padded
    with PAD; ``true_len`` (P,); ``answers`` (P,); ``budgets`` (P * G,) for
    the group-expanded rows (row r belongs to prompt r // G)."""

    prompts: np.ndarray
    true_len: np.ndarray
    answers: np.ndarray
    budgets: np.ndarray

    def prompt_tokens(self, group_size: int) -> int:
        """Non-pad prompt tokens over the group-expanded rows."""
        return int(self.true_len.sum()) * group_size


def van_der_corput(i: int) -> float:
    """The i-th point (i >= 0) of the base-2 van der Corput sequence."""
    x, denom, i = 0.0, 1.0, i + 1
    while i:
        denom *= 2
        i, bit = divmod(i, 2)
        x += bit / denom
    return x


def stratified(n: int, offset: float) -> np.ndarray:
    """n quantile levels, one in each of n equal strata of [0, 1)."""
    return (np.arange(n) + offset) / n


def prompt_lengths(u: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Log-uniform lengths on [lo, hi] at quantile levels u."""
    return np.clip(np.round(lo * (hi / lo) ** u), lo, hi).astype(np.int32)


def budgets_at(u: np.ndarray, mix) -> np.ndarray:
    """Mixture of uniform parts ``[[share, lo, hi], ...]`` at quantile levels
    u (the inverse of its distribution function)."""
    shares = np.asarray([m[0] for m in mix], np.float64)
    if not math.isclose(shares.sum(), 1.0, abs_tol=1e-9):
        raise ValueError(f"budget shares sum to {shares.sum()}, not 1")
    edges = np.concatenate([[0.0], np.cumsum(shares)])
    part = np.clip(np.searchsorted(edges, u, side="right") - 1, 0, len(mix) - 1)
    out = np.empty(len(u), np.int32)
    for k, (share, lo, hi) in enumerate(mix):
        sel = part == k
        within = (u[sel] - edges[k]) / share
        out[sel] = np.round(lo + within * (hi - lo))
    return out


def cycle(mix: dict) -> int:
    """Iterations in one whole cycle of the traffic's sizes: 4, whatever the
    mix. Any 4 consecutive van der Corput points fall one in each quarter of
    [0, 1) (the two lowest bits of ``i + 1`` take all four values and set
    the point's first two binary digits), so any 4 consecutive iterations,
    from any start, draw their ``n`` lengths at levels one in each of the
    ``4 * n`` strata of 1/(4·n): a window of whole cycles holds the mixture
    evenly, wherever it starts."""
    return 4


def batches(mix: dict, *, seed: int, prompts_per_iter: int, vocab_size: int):
    """Endless iterator of :class:`Batch`, one per RL iteration."""
    rng = np.random.default_rng(seed)
    g, width = mix["group_size"], mix["prompt_width"]
    lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
    if hi > width:
        raise ValueError(f"prompts up to {hi} tokens exceed the width {width}")
    n = prompts_per_iter * g
    i = 0
    while True:
        v = van_der_corput(i)
        lens = rng.permutation(prompt_lengths(
            stratified(prompts_per_iter, v), lo, hi))
        bud = rng.permutation(budgets_at(stratified(n, v), mix["budgets"]))
        bud = np.minimum(bud, mix["max_new"]).astype(np.int32)
        prompts = np.full((prompts_per_iter, width), PAD, np.int32)
        for r, length in enumerate(lens):
            prompts[r, :length] = rng.integers(FIRST_ID, vocab_size, length)
        answers = rng.integers(0, 199, prompts_per_iter).astype(np.int32)
        yield Batch(prompts, lens, answers, bud)
        i += 1
