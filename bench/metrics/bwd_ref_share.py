"""Device time of the XLA reference backward of the kernels over the
window: the actor step's ops that come from the ``flash_attention_bwd``,
``rmsnorm_bwd`` and ``ssd_bwd`` named scopes, as the step's compiled text
maps its instructions to scopes. What a Pallas backward would replace."""
import re

LAYER = "kernels (kernels/*.py through kernels/ops.py)"
UNIT = "fraction"
SOURCE = "device_trace"
MOVES = "tokens_per_s_per_chip"
BETTER = "lower"
PROGRAM = "jit_step"
SCOPES = re.compile(r"(flash_attention|rmsnorm|ssd)_bwd")


def read(ctx):
    from bench.trace import is_container, op_name

    scopes = ctx.scopes.get(PROGRAM)
    if not scopes:
        return None
    t = ctx.trace.op_seconds(
        lambda op, mod: mod == PROGRAM and not is_container(op)
        and bool(SCOPES.search(scopes.get(op_name(op), ""))))
    return t / ctx.window_s
