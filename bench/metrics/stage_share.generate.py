"""Device time of the generation programs over the window: the continuous
engine's jitted halves (refill prefill, continuation feed, decode burst),
matched by the program names below."""
import re

LAYER = "generation (rl/rollout_engine.py)"
UNIT = "fraction"
SOURCE = "device_trace"
MOVES = "tokens_per_s_per_chip"
BETTER = "higher"
PROGRAMS = re.compile(r"^jit_(refill|cont|burst)$")


def read(ctx):
    t = ctx.trace.module_seconds(lambda name, ops: bool(PROGRAMS.match(name)))
    return t / ctx.window_s
