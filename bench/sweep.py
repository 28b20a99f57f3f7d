#!/usr/bin/env python3
"""Rate sweep of an open-loop cell, to find the highest rate the system
sustains (its knee).  One process, one set-up, one window per rate:

    python bench/sweep.py --workload xl256-taa-poisson --seed 5 \\
        --seconds 40 --rates 0.6,0.8,1.0,1.2

Prints, per rate, the requests due and completed in the window, the
latency median and 90th percentile from due time, and the requests still
open when the window closed (a backlog that grows with the window marks a
rate above the knee).  The cell's traffic file fixes its rate; this tool
only informs that choice.
"""
import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

import harness  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if cell.traffic["arrivals"] != "poisson":
        raise SystemExit("a rate sweep needs an open-loop cell")
    sys.path.insert(0, str(harness.SRC))
    harness.configure_jax()
    harness.find_devices(cell.chips)
    meter = harness.CompileMeter()
    system = harness.build_system(cell, args.seed)
    harness.warm_up(system)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        w = harness.run_window(system, args.seed + i, args.seconds,
                               meter=meter, rate_per_s=rate)
        served = harness.outcome(w.sent)
        lat = [s.ticket.completed_time - s.due for s, r in served
               if r is not None]
        open_at_close = sum(1 for s, r in served if r is None or
                            s.ticket.completed_time > w.t1)
        print(json.dumps({
            "rate_per_s": rate, "due": len(served),
            "completed_in_window": sum(
                1 for s, r in served
                if r is not None and s.ticket.completed_time <= w.t1),
            "open_at_close": open_at_close,
            "failed": sum(1 for _, r in served if r is None),
            "latency_p50_s": float(np.percentile(lat, 50)) if lat else None,
            "latency_p90_s": float(np.percentile(lat, 90)) if lat else None,
            "iters_p50": float(np.median([r.iters for _, r in served
                                          if r is not None] or [0])),
            "chunks": w.chunks, "window_compiles": w.compiles}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
