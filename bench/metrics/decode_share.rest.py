"""Device time of the decode burst's leaf ops under none of the model's
``mixer``, ``mlp`` and ``head`` named scopes (``models/lm.py``), over the
window: the decode loop's work outside the model's layers, where copies,
slices and relayouts of the KV arena land, beside the loop's control and the
embedding. ``bench/program_trace.py`` reads each op's name stack."""
LAYER = "generation (rl/rollout_engine.py)"
UNIT = "fraction"
SOURCE = "device_trace"
MOVES = "tokens_per_s_per_chip"
BETTER = "lower"


def read(ctx):
    from bench import program_trace
    from bench import trace as tr

    red = program_trace.reduction_for(ctx)
    if red is None:
        return None
    burst = program_trace.BURST
    scoped = [program_trace.scope_seconds(red, burst, s)
              for s in program_trace.LAYER_SCOPES]
    if None in scoped:
        return None
    leaves = red.op_seconds(lambda op, mod: mod == burst
                            and not tr.is_container(op))
    return (leaves - sum(scoped)) / ctx.window_s
