#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \\
        [--faults 11,12,13]

For every seed, in one process: build the cell's pipeline, drive its set-up
steps exactly as a benchmark run does, free it, and run the plain float32
reference over what it produced. That gives the sound readings, whose
largest over the seeds is each number's lower reading. For the seeds named
by ``--faults`` it also reads, against the same float32 reference:

- ``control``: the reference in float8 (e4m3, one scale per tensor) put in
  the program's place, one precision step below the configuration's
  bfloat16;
- ``half_batch``: the reference in the program's place with the loss taken
  over half of the rows, the mean over the rest;
- ``token``: the program's own rollouts with one response token of every
  row altered after it was sampled (its log-prob kept).

A state left unchanged reads 1 on the update and gradient numbers by their
definition and needs no run. One JSON line per seed and kind goes to
standard output.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]


def altered(steps, vocab: int):
    """The rollouts with the middle response token of every row replaced."""
    import numpy as np

    out = []
    for s in steps:
        tokens = s["tokens"].copy()
        for r, row in enumerate(s["mask"]):
            pos = np.flatnonzero(row)
            t = pos[len(pos) // 2]
            tokens[r, t] = 3 + (int(tokens[r, t]) - 3 + vocab // 2) % (
                vocab - 3)
        out.append(dict(s, tokens=tokens))
    return out


def as_program(ref: dict, mask) -> dict:
    """A reference run's numbers in the program's place."""
    return {"mask": mask, "old_lp": ref["old_lp"], "ref_lp": ref["ref_lp"],
            "loss": ref["loss"], "grad_norms": ref["grad_norms"],
            "delta_norms": ref["delta_norms"]}


def readings(cell: dict, seed: int, with_faults: bool):
    from bench import harness, reference

    sess = harness.Session(cell, seed)
    sess.setup()
    sess.release()
    arch = reference.arch_of(cell["config_spec"])
    rl = dict(cell["rl"], group_size=cell["traffic_spec"]["group_size"])
    steps = harness.reference_steps(sess.rec.steps)
    prog = sess.program_side()
    t = time.perf_counter()
    ref = reference.follow(arch, seed, steps, rl)
    yield "sound", reference.compare(prog, ref), time.perf_counter() - t
    if not with_faults:
        return
    mask = prog["mask"]
    ctrl = reference.follow(arch, seed, steps, rl, precision="fp8")
    yield "control", reference.compare(as_program(ctrl, mask), ref), None
    half = reference.follow(arch, seed, steps, rl, half_batch=True)
    yield "half_batch", reference.compare(as_program(half, mask), ref), None
    alt = reference.follow(arch, seed, altered(steps, arch["vocab"]), rl)
    yield "token", reference.compare(prog, alt), None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)

    from bench import catalog, harness

    cell = catalog.load_cell(args.workload)
    harness.prepare(True, cell["chips"])
    faults = {int(s) for s in args.faults.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        for kind, nums, ref_s in readings(cell, seed, seed in faults):
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": kind, "reference_s": ref_s, **nums}),
                  flush=True)
    print(f"calibrate: {time.perf_counter() - T0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
