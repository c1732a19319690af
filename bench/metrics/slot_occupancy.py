"""Active slot-steps over lane-steps of the continuous engine, summed over
the window's iterations from its ``last_stats`` counts (never a mean of the
per-iteration ratios)."""
LAYER = "generation (rl/rollout_engine.py)"
UNIT = "fraction"
SOURCE = "program_counter"
MOVES = "tokens_per_s_per_chip"
BETTER = "higher"


def read(ctx):
    lanes = ctx.counts["lane_steps"]
    return ctx.counts["occupied_lane_steps"] / lanes if lanes else None
