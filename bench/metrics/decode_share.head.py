"""Device time of the decode burst's leaf ops under the model's ``head``
named scope (``models/lm.py``: the head matmul and the fused sampler), over
the window. ``bench/program_trace.py`` reads each op's name stack."""
LAYER = "generation (rl/rollout_engine.py)"
UNIT = "fraction"
SOURCE = "device_trace"
MOVES = "tokens_per_s_per_chip"
BETTER = "lower"
SCOPE = "head"


def read(ctx):
    from bench import program_trace

    return program_trace.burst_share(ctx, SCOPE)
