"""Readings for setting a cell's limits, many seeds in one process.

    python3 benchmarks/prove.py --workload <cell> --seeds 1,2,3 [--controls 3]

For every seed the cell's driver runs the program once at the cell's own
size and compares it with the plain reference (`driver.prove`); for the
first `--controls` seeds it also reads the control and the planted faults.
One JSON object per seed on standard output. The benchmark's own runs never
run this; the limits in the workload files were set from its output
(PERF.md gives the readings).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmarks.harness import (  # noqa: E402
    device_info,
    log,
    make_context,
    open_cell,
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--root", default=CHECKOUT)
    args = ap.parse_args(argv)

    plan, driver = open_cell(args.root, args.workload, args.rehearsal)
    import jax

    device = device_info(jax)
    if not args.rehearsal and device["platform"] != "tpu":
        log(f"no accelerator: {device}")
        return 3
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = make_context(plan, seed, args.seconds, False, args.rehearsal)
        out = driver.prove(ctx, controls=n < args.controls)
        print(json.dumps({"seed": seed, "device": device["kind"], **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
