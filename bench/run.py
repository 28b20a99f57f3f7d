#!/usr/bin/env python3
"""Chip benchmark of the diffusion serving path: one cell, one measured window.

    python bench/run.py --workload xl256-taa-poisson --seed 7 \\
        --seconds 51 --trace 0

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  Without a TPU, or with fewer chips, it exits non-zero and
prints no result.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiled window.  Earlier lines
of standard output report the compilations inside the window, the load
generator's lateness and the traffic's parameters; the last line is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, ``breakdown`` when traced, and ``checks`` last: each compared
number with its limit, which also end standard error).
"""
import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import harness  # noqa: E402


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.load_cell(args.workload)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         PROCESS_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
