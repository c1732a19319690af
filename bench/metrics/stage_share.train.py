"""Device time of the actor-step program over the window (loss, backward,
clip and AdamW in one executable, ``trainer.make_actor_step``'s ``step``)."""
import re

LAYER = "training (rl/trainer.py, optim/adamw.py)"
UNIT = "fraction"
SOURCE = "device_trace"
MOVES = "tokens_per_s_per_chip"
BETTER = "higher"
PROGRAMS = re.compile(r"^jit_step$")


def read(ctx):
    t = ctx.trace.module_seconds(lambda name, ops: bool(PROGRAMS.match(name)))
    return t / ctx.window_s
