"""Device time of the reference log-prob forward over the window. The
pipeline jits it from a lambda, as it does the reward and advantage
engines; of those programs it is the one that runs Pallas kernels."""
import re

LAYER = "training (rl/trainer.py, optim/adamw.py)"
UNIT = "fraction"
SOURCE = "device_trace"
MOVES = "tokens_per_s_per_chip"
BETTER = "higher"
PROGRAMS = re.compile(r"^jit__lambda$")


def read(ctx):
    from bench.trace import is_kernel

    t = ctx.trace.module_seconds(
        lambda name, ops: bool(PROGRAMS.match(name))
        and any(is_kernel(op) for op in ops))
    return t / ctx.window_s
