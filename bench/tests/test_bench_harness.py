"""Whole runs of the harness on the CPU at a reduced size: a sound run is
correct, and a run whose timed path is broken underneath is not.

The look for a chip is skipped (``devices`` is passed); everything else a
run does on the chip happens here: weights, warm-up, a window of the cell's
traffic through the program's serving loop, drain, the reference check and
the result line.
"""
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import harness  # noqa: E402

SMALL = dict(num_layers=2, d_model=64, num_heads=4, head_dim=16, d_ff=128,
             latent_dim=16, num_tokens=16, num_classes=10)
CELLS = {
    "xl256-taa-poisson": dict(rate_per_s=8.0, T=8),
    "xl512-seq-offline": dict(T=6, client_stagger_s=0.02),
}
SEED = 2**31 + 3


def small_cell(name):
    cell = harness.load_cell(name)
    cell.config = dict(cell.config, **SMALL)
    cell.traffic = dict(cell.traffic, drain_s=3.0,
                        check={"requests": 3, "block": 4}, **CELLS[name])
    return cell


def run(cell, trace=False):
    import jax
    return harness.run(cell, SEED, 1.5, trace, time.monotonic(),
                       devices=jax.devices(), out=lambda *_: None)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(name):
    result = run(small_cell(name))
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == {"step_gap", "noise_row", "unserved"}
    assert result["device"]["platform"] == "cpu"
    json.dumps(result)


def test_traced_run_reports_layer_metrics():
    """On the CPU only the counters read; no peak, so no device metric."""
    result = run(small_cell("xl256-taa-poisson"), trace=True)
    assert set(result["metrics"]) == {"queue_wait_p50_s",
                                      "lane_useful_frac.serve",
                                      "solver_iters_p50"}
    assert 0 < result["metrics"]["lane_useful_frac.serve"]["value"] <= 1


@pytest.mark.parametrize("name,fault", [
    ("xl256-taa-poisson", "frozen"),
    ("xl512-seq-offline", "half_lanes"),
    ("xl256-taa-poisson", "altered"),
    ("xl512-seq-offline", "altered"),
    ("xl256-taa-poisson", "loose_tol=30"),
    ("xl256-taa-poisson", "early_stop"),
])
def test_broken_timed_path_is_not_correct(name, fault):
    """A step that returns its state unchanged, half the lanes left out of
    the step, an answer altered where it is produced, a solver tolerance
    loosened until served rows leave the stated one (30x here; the last
    iteration lands rows far below a tolerance, so 10x still meets it at
    T=8), and an iteration budget cut to T // 4, half of what a sound solve
    takes: each reads ``correct`` false."""
    import faults
    with faults.planted(fault):
        result = run(small_cell(name))
    assert result["correct"] is False


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_fails_the_limit(name):
    """The control, the reference in bfloat16 in the program's place, reads
    above the cell's step_gap limit on the served rows of a sound run, which
    read below it."""
    import gc
    import jax
    import jax.numpy as jnp
    cell = small_cell(name)
    system = harness.build_system(cell, SEED)
    harness.warm_up(system)
    window = harness.run_window(system, SEED, 1.5)
    system.engine = None
    gc.collect()
    prog = harness.correctness(system, window, SEED)
    ctl = harness.correctness(system, window, SEED, dtype=jnp.bfloat16)
    limit = cell.limits["step_gap"]
    assert prog["checked"] > 0 and ctl["checked"] == prog["checked"]
    assert prog["step_gap"] < limit < ctl["step_gap"]
    del jax
