#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, for one cell.

    python bench/control.py --workload cartpole-rollout --seeds 1 2 3

For each seed, in one process: the cell is set up and run for a short
window until its checked chunks are done, as `run.py` does; then for each
checked chunk it prints two readings: the program's (the window's own
transitions against the reference) and the control's (the reference
itself, computed in bfloat16, the precision below the configuration's
float32, put in the program's place). A limit lies between the largest
program reading and the smallest control reading.

It needs the cell's chips, like `run.py`, and is not part of a run.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def readings(cell, control_dtype="bfloat16"):
    """[(program gap, program mismatches, control gap, control
    mismatches)] over the checked chunks of a finished window."""
    import jax
    import jax.numpy as jnp

    control_dtype = jnp.dtype(control_dtype)
    ref = cell.ref
    judge = jax.jit(lambda c, a, o, after: ref.check(c, a, o,
                                                     ref.lanes(after)))

    def control(c, a):
        out, after = ref.run(c, a, control_dtype)
        return ref.check(c, a, out, after)

    control = jax.jit(control)
    rows = []
    for before, acts, out, after in cell.checked_chunks():
        g, b = judge(before, acts, out, after)
        cg, cb = control(before, acts)
        rows.append((float(g), int(b), float(cg), int(cb)))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    run.quiet_tpu_logs()
    found = run.resolve(args.workload, (run.ROOT,))
    devices = run.require_chips(int(found["cell"]["chips"]))
    run.enable_compile_cache()
    for seed in args.seeds:
        cell = found["consumer"].Cell(found["config"], found["traffic"],
                                      found["reference"], seed, devices)
        cell.warm()
        cell.window(args.seconds)
        cell.close()
        for g, b, cg, cb in readings(cell):
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "program_gap": g, "program_mismatches": b,
                              "control_gap": cg, "control_mismatches": cb}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(run.ROOT / "src"))
    sys.exit(main())
