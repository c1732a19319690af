"""Model FLOPs of the window's iterations (``bench/flops.py``, from the
non-pad tokens and the shapes; recomputation and padding do not count) over
window x chips x the chip's bf16 peak (``bench/peaks.json``)."""
LAYER = "device"
UNIT = "fraction"
SOURCE = "host_clock"
MOVES = "tokens_per_s_per_chip"
BETTER = "higher"


def read(ctx):
    return ctx.model_flops() / (ctx.window_s * ctx.chips
                                * ctx.peaks["bf16_flops"])
