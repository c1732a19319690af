"""Executables JAX built or loaded inside the measured window (JAX's
``backend_compile`` monitoring event, cache hits included). Every shape is
warmed in set-up, so it should read 0; a program that recompiles in the
window shows here before it shows in the throughput."""
LAYER = "entry (core/pipeline.py Pipeline.run)"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "tokens_per_s_per_chip"
BETTER = "lower"


def read(ctx):
    return ctx.counts["compiles"]
