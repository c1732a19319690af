"""Model FLOPs of an RL iteration, from the configuration's shapes and the
non-pad token counts alone.

A matmul of a token against an (m x n) weight costs 2mn. Per non-pad token:

- generation (prefill of the prompt, then one decode step per response
  token but the last): one forward;
- the reference log-prob stage: one forward over the whole sequence;
- the actor step: three forwards (forward and backward) over the sequence.

A forward is the layout kind's own count (``forward_flops`` of
``bench/models/<kind>.py``): 2N a token, N the matmul parameters of the
real model (real heads, real vocabulary: padding does not count; the
embedding lookup is no matmul, a tied head counts once), plus its sequence
mixing, which may grow with the context each token reads. Recomputation
does not count.
"""
from __future__ import annotations

from typing import Iterable

import numpy as np

from bench import catalog


def matmul_params(lay: dict) -> int:
    """N: weights a token multiplies against, real sizes only."""
    return catalog.model(lay["kind"]).matmul_params(lay)


def iteration_flops(lay: dict, prompt_lens: Iterable[int],
                    response_lens: Iterable[int]) -> float:
    """Model FLOPs of one RL iteration over its group-expanded rows."""
    forward = catalog.model(lay["kind"]).forward_flops
    p = np.asarray(list(prompt_lens), np.float64)
    r = np.asarray(list(response_lens), np.float64)
    gen = forward(lay, p + np.maximum(r - 1, 0))
    fwd = forward(lay, p + r)
    return gen + fwd + 3 * fwd
