"""Find the knee of the serving cell: the highest offered rate at which
the server keeps up over a window.

    python3 -m bench.knee --workload pn2c-lpcn-serve --seed <n> \
        --seconds 20 --rates 20,30,40,50,60

One process sets the cell up once (with its open-loop warm-up) and plays
the open loop at each rate in turn (a fresh schedule per rate, same
seed).  A rate keeps up when
every request is answered and the requests due in the window's last
fifth wait, at the median, no more than a quarter longer than those due
in its first fifth: a backlog that grows over the window shows as
latency that rises.  The knee is the highest rate that keeps up; the cell runs at
four fifths of it, written as a number into its traffic file.  Results
go to standard output, one JSON line per rate, then the knee.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from bench import families, run


def keeps_up(latency_s: np.ndarray) -> tuple[bool, float, float]:
    n = len(latency_s)
    fifth = max(n // 5, 1)
    first = float(np.median(latency_s[:fifth]))
    last = float(np.median(latency_s[-fifth:]))
    ok = bool(np.isfinite(latency_s).all() and last <= 1.25 * first)
    return ok, first, last


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    cell = run.load_cell(args.workload)
    run.set_libtpu_flags(cell)
    from bench import program
    program.import_program()
    run.require_devices(cell["workload"]["chips"], False)
    run.configure_jax(cell["config"], False)
    drv = run.driver_class(cell["traffic"])(
        cell, args.seed, families.of(cell["config"]), args.seconds)
    drv.setup()
    knee = None
    for rate in rates:
        drv.plan(rate)
        drv.window(args.seconds)
        order = np.argsort(drv.due)
        ok, first, last = keeps_up(drv.latency_s[order])
        e2e = drv.end_to_end()
        print(json.dumps({
            "rate_hz": rate, "keeps_up": ok,
            "served_clouds_per_s": e2e["served_clouds_per_s"],
            "p50_ms": 1e3 * float(np.median(drv.latency_s)),
            "p95_ms": e2e["serve_p95_ms"],
            "first_fifth_p50_ms": 1e3 * first,
            "last_fifth_p50_ms": 1e3 * last,
            "client_late_p95_ms": 1e3 * float(np.percentile(drv.late_s, 95)),
            "unanswered": len(drv.failed)}), flush=True)
        if ok:
            knee = rate
    print(json.dumps({"knee_hz": knee,
                      "cell_rate_hz": None if knee is None else 0.8 * knee}))
    drv.server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
