"""Device time of the decode burst's leaf ops under the model's ``mixer``
named scope (``models/lm.py``: the block's attention or SSD mixer and its
residual add; the layer scan's write of the new K/V into the arena lies
outside it), over the window. ``bench/program_trace.py`` reads each op's
name stack."""
LAYER = "generation (rl/rollout_engine.py)"
UNIT = "fraction"
SOURCE = "device_trace"
MOVES = "tokens_per_s_per_chip"
BETTER = "lower"
SCOPE = "mixer"


def read(ctx):
    from bench import program_trace

    return program_trace.burst_share(ctx, SCOPE)
