"""Device time of the decode burst's leaf ops under the model's ``mlp``
named scope (``models/lm.py``: the block's dense or MoE MLP and its
residual add), over the window. ``bench/program_trace.py`` reads each op's
name stack."""
LAYER = "generation (rl/rollout_engine.py)"
UNIT = "fraction"
SOURCE = "device_trace"
MOVES = "tokens_per_s_per_chip"
BETTER = "lower"
SCOPE = "mlp"


def read(ctx):
    from bench import program_trace

    return program_trace.burst_share(ctx, SCOPE)
