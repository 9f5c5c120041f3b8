"""Readings from which a cell's limits are set (run on the chip).

    python3 -m bench.readings --workload <cell> --seeds 1,2,3 \
        --seconds 3 [--controls high,bf16]

For every seed, in one process: the cell's set-up and a short window on
the timed path, then the gap of the program's answers to the reference
(the lower reading comes from these), and the gap of each control, the
reference computed at a lower precision in the program's place, on the
same inputs (the upper reading comes from these).  The control answers
with each boundary tie where the rounding put it (its first reading).  One JSON line per
seed, then the largest program gap and the smallest gap of each control.
Each seed's line also gives the program's gap to the plain reading
alone, and how many clouds have readings that differ from it.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from bench import families, run
from bench import refcore as rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--controls", default="high")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the tiny size of run --rehearse")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = [c for c in args.controls.split(",") if c]

    cell = run.load_cell(args.workload)
    if args.rehearse:
        run.shrink(cell)
    else:
        run.set_libtpu_flags(cell)
    from bench import program
    program.import_program()
    run.require_devices(cell["workload"]["chips"], args.rehearse)
    run.configure_jax(cell["config"], args.rehearse)
    fam = families.of(cell["config"])
    mode = cell["traffic"]["engine"]["mode"]
    worst, least = 0.0, {c: np.inf for c in controls}
    for seed in seeds:
        drv = run.driver_class(cell["traffic"])(cell, seed, fam, args.seconds)
        drv.setup()
        drv.window(args.seconds)
        drv.release()
        clouds, keys = drv.check_inputs()
        ref = fam.reference(cell["config"], drv.weights, clouds, keys, mode)
        values, attempted, _ = drv.check(ref)
        line = {"seed": seed, "attempted": attempted,
                "program": values,
                "program_plain_reading": drv.check(ref[:, :1])[0],
                "clouds_with_readings": int((np.abs(ref - ref[:, :1])
                                             .max((1, 2)) > 0).sum())}
        for c in controls:
            ctl = fam.reference(cell["config"], drv.weights, clouds, keys,
                                mode, precision=c)[:, 0]
            g = rc.rel_gap(ctl, ref)
            line[c] = {"max": float(g.max()), "median": float(np.median(g)),
                       "min": float(g.min())}
            least[c] = min(least[c], float(g.max()))
        worst = max(worst, values["logit_gap"])
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": args.workload, "lower_reading": worst,
                      "control_upper_readings": least}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
