"""Faults planted in the timed path, to show that ``correct`` catches them.

Each fault wraps one ``SamplingEngine`` stepwise method, the path the
measured window drives.  The tests plant them at a reduced size on the
CPU; ``control.py --fault NAME[=ARG]`` reads them on the chip at the cell's
own size.  The benchmark's own runs never plant one.

    frozen        a step that returns its state unchanged
    half_lanes    the step leaves half the lanes out
    altered       a served answer altered where it is produced (harvest)
    loose_tol=F   the solver tolerance loosened F times (default 10)
    early_stop=N  each request's iteration budget cut to N (default T // 4,
                  half of what a sound solve of the DiT cells takes)
"""
from __future__ import annotations

import contextlib
import dataclasses


def _frozen(real, _arg):
    def step(self, bank):
        old = bank.state
        real(self, bank)
        bank.state, bank.summary, bank.poll_cache = old, None, None
    return step


def _half_lanes(real, _arg):
    def step(self, bank):
        import jax
        import jax.numpy as jnp
        old = bank.state
        real(self, bank)
        keep = jnp.arange(bank.slots) < bank.slots // 2

        def pick(new, o):
            return jnp.where(keep.reshape((-1,) + (1,) * (new.ndim - 1)),
                             new, o)
        bank.state = jax.tree.map(pick, bank.state, old)
        bank.summary, bank.poll_cache = None, None
    return step


def _altered(real, _arg):
    def harvest(self, bank):
        out = real(self, bank)
        for _, res in out:
            traj = res.trajectory.copy()
            traj[0] += 0.05 * abs(traj[0]).mean()
            res.trajectory, res.x0 = traj, traj[0]
        return out
    return harvest


def _loose_tol(real, arg):
    factor = 10.0 if arg is None else float(arg)

    def refill(self, bank, lanes, requests):
        requests = [dataclasses.replace(
            r, tau=factor * (self.spec.tau if r.tau is None else r.tau))
            for r in requests]
        return real(self, bank, lanes, requests)
    return refill


def _early_stop(real, arg):
    def refill(self, bank, lanes, requests):
        cap = self.coeffs.T // 4 if arg is None else int(arg)
        requests = [dataclasses.replace(r, max_iters=cap) for r in requests]
        return real(self, bank, lanes, requests)
    return refill


FAULTS = {"frozen": ("stepwise_step", _frozen),
          "half_lanes": ("stepwise_step", _half_lanes),
          "altered": ("stepwise_harvest", _altered),
          "loose_tol": ("stepwise_refill", _loose_tol),
          "early_stop": ("stepwise_refill", _early_stop)}


@contextlib.contextmanager
def planted(spec: str):
    """Plant the fault ``NAME`` or ``NAME=ARG`` for the ``with`` block."""
    from repro.sampling.engine import SamplingEngine
    name, _, arg = spec.partition("=")
    method, make = FAULTS[name]
    real = getattr(SamplingEngine, method)
    setattr(SamplingEngine, method, make(real, arg or None))
    try:
        yield
    finally:
        setattr(SamplingEngine, method, real)
