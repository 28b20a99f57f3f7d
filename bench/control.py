#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

For each seed, in one process: a short window at the cell's own load, then
the compared numbers of the program's served trajectories and, at the same
rows, of the control, the reference computed one precision below the
configuration's (bfloat16 for float32).  ``--faults`` adds, per seed, a
window with each planted fault (``faults.py``) and its reading.  One line
per seed and reading:

    python bench/control.py --workload xl256-taa-poisson --seconds 15 \\
        --seeds 101,102,103 --faults early_stop,loose_tol=10

The benchmark's own runs never run the control.
"""
import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import faults  # noqa: E402
import harness  # noqa: E402


def read(cell, seed: int, seconds: float, meter, reading: str):
    """One short window; prints the compared numbers of what it served."""
    t0 = time.monotonic()
    system = harness.build_system(cell, seed)
    harness.warm_up(system)
    window = harness.run_window(system, seed, seconds, meter=meter)
    attempted, failed = harness.attempted_failed(system, window)
    iters = [int(r.iters) for _, r in harness.outcome(window.sent)
             if r is not None]
    system.engine = None
    gc.collect()
    t1 = time.monotonic()
    numbers = harness.correctness(system, window, seed)
    print(json.dumps({"seed": seed, "reading": reading, **numbers,
                      "attempted": attempted, "failed": failed,
                      "iters_max": max(iters, default=None),
                      "window_compiles": window.compiles,
                      "run_s": t1 - t0,
                      "reference_s": time.monotonic() - t1}), flush=True)
    return system, window


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated seeds")
    p.add_argument("--faults", default="",
                   help="comma-separated faults to plant, NAME or NAME=ARG")
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.SRC))
    harness.configure_jax()
    import jax.numpy as jnp
    harness.find_devices(cell.chips)
    meter = harness.CompileMeter()
    for seed in [int(s) for s in args.seeds.split(",")]:
        system, window = read(cell, seed, args.seconds, meter, "program")
        t0 = time.monotonic()
        ctl = harness.correctness(system, window, seed, dtype=jnp.bfloat16)
        print(json.dumps({"seed": seed, "reading": "control", **ctl,
                          "control_s": time.monotonic() - t0}), flush=True)
        del system, window
        for fault in filter(None, args.faults.split(",")):
            with faults.planted(fault):
                read(cell, seed, args.seconds, meter, f"fault {fault}")
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
