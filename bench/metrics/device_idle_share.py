"""1 - (union of the intervals in which an operation ran on the device) /
window, averaged over the chips the cell uses."""
LAYER = "device"
UNIT = "fraction"
SOURCE = "device_trace"
MOVES = "tokens_per_s_per_chip"
BETTER = "lower"


def read(ctx):
    return 1.0 - ctx.trace.busy_s / ctx.window_s
