"""Device time of the Pallas kernels (Mosaic custom calls,
``tpu_custom_call``) over the window: flash attention, flash decode, the
fused sampler, SSD, rmsnorm."""
LAYER = "kernels (kernels/*.py through kernels/ops.py)"
UNIT = "fraction"
SOURCE = "device_trace"
MOVES = "tokens_per_s_per_chip"
BETTER = "higher"


def read(ctx):
    from bench.trace import is_kernel

    return ctx.trace.op_seconds(lambda op, mod: is_kernel(op)) / ctx.window_s
