"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Default parameters are sized for
CPU (small trained DiT, T = 25-100); pass --full for the paper-scale step
counts (same code, longer run).
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help="comma-separated subset (fig1,fig2,table1,fig4,fig5,"
                        "fig6,fig7,serve,serve_async,roofline)")
    p.add_argument("--full", action="store_true",
                   help="paper-scale step counts (T=100 everywhere)")
    args = p.parse_args()

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    from benchmarks import (figure1_order_k, figure2_taa, table1_scenarios,
                            figure4_window, figure5_traj_init,
                            figure6_safeguard, figure7_grid, roofline_table,
                            serving_async, serving_throughput)

    suites = {
        "fig1": lambda: figure1_order_k.run(T=100 if args.full else 50),
        "fig2": lambda: figure2_taa.run(T=100 if args.full else 50),
        "table1": lambda: table1_scenarios.run(
            scenarios=(("ddim", 25), ("ddim", 50), ("ddim", 100), ("ddpm", 100))
            if args.full else (("ddim", 25), ("ddim", 50), ("ddpm", 50))),
        "fig4": lambda: figure4_window.run(T=100 if args.full else 60),
        "fig5": lambda: figure5_traj_init.run(T=50),
        "fig6": lambda: figure6_safeguard.run(T=50),
        "fig7": lambda: figure7_grid.run(T=50),
        "serve": lambda: serving_throughput.run(T=25),
        "serve_async": lambda: serving_async.run(T=25),
        "roofline": roofline_table.run,
    }
    chosen = args.only.split(",") if args.only else list(suites)
    print("name,us_per_call,derived")
    failed = 0
    for name in chosen:
        try:
            for row in suites[name]():
                print(f"{row[0]},{row[1]:.1f},{row[2]}", flush=True)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failed += 1
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
