"""Model FLOPs of an RL iteration, from the configuration's shapes and the
non-pad token counts alone.

A matmul of a token against an (m x n) weight costs 2mn. Per non-pad token:

- generation (prefill of the prompt, then one decode step per response
  token but the last): one forward;
- the reference log-prob stage: one forward over the whole sequence;
- the actor step: three forwards (forward and backward) over the sequence.

The forward of a token is 2N, N the matmul parameters of the real model
(real heads, real vocabulary: padding does not count; the embedding lookup
is no matmul, a tied head counts once), plus its sequence mixing: causal
attention reads every earlier non-pad token, 4 * heads * head_dim each
(scores and values); an SSD layer updates and reads its state, 4 *
d_inner * d_state, after a depthwise conv of 2 * conv * (d_inner + 2 *
groups * d_state). Recomputation does not count.
"""
from __future__ import annotations

from typing import Iterable

import numpy as np


def matmul_params(lay: dict) -> int:
    """N: weights a token multiplies against, real sizes only."""
    d, v, layers = lay["d_model"], lay["vocab_size"], lay["num_layers"]
    if lay["kind"] == "dense":
        h, kvh, hd = lay["num_heads"], lay["num_kv_heads"], lay["head_dim"]
        attn = d * h * hd * 2 + d * kvh * hd * 2
        per_layer = attn + 3 * d * lay["d_ff"]
    else:
        din = lay["ssm_expand"] * d
        gn = lay["ssm_ngroups"] * lay["ssm_state"]
        heads = din // lay["ssm_headdim"]
        per_layer = d * (2 * din + 2 * gn + heads) + din * d
    return layers * per_layer + d * v


def mixing_per_context(lay: dict) -> int:
    """Forward FLOPs of one layer's sequence mixing per earlier token read
    (attention); 0 for a state-space layer."""
    if lay["kind"] == "dense":
        return 4 * lay["num_heads"] * lay["head_dim"]
    return 0


def mixing_per_token(lay: dict) -> int:
    """Forward FLOPs of one layer's sequence mixing per token that do not
    grow with the context (the SSD state update and read, its conv)."""
    if lay["kind"] == "dense":
        return 0
    din = lay["ssm_expand"] * lay["d_model"]
    n, g, k = lay["ssm_state"], lay["ssm_ngroups"], lay["ssm_conv"]
    return 4 * din * n + 2 * k * (din + 2 * g * n)


def forward_flops(lay: dict, tokens: int, contexts: int) -> float:
    """Forward FLOPs of ``tokens`` non-pad tokens that together read
    ``contexts`` earlier tokens (themselves included)."""
    per_token = 2 * matmul_params(lay) + lay["num_layers"] * mixing_per_token(
        lay)
    return float(tokens) * per_token + float(contexts) * lay[
        "num_layers"] * mixing_per_context(lay)


def _contexts(n: np.ndarray) -> np.ndarray:
    """Sum over a sequence of n tokens of how many tokens each reads."""
    n = np.asarray(n, np.float64)
    return n * (n + 1) / 2


def iteration_flops(lay: dict, prompt_lens: Iterable[int],
                    response_lens: Iterable[int]) -> float:
    """Model FLOPs of one RL iteration over its group-expanded rows."""
    p = np.asarray(list(prompt_lens), np.float64)
    r = np.asarray(list(response_lens), np.float64)
    gen_tokens = p + np.maximum(r - 1, 0)
    full = p + r
    gen = forward_flops(lay, gen_tokens.sum(), _contexts(gen_tokens).sum())
    fwd = forward_flops(lay, full.sum(), _contexts(full).sum())
    return gen + fwd + 3 * fwd
