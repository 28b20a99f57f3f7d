"""Unified `repro.sampling` API tests: spec registry resolution, engine
batched execution ≡ the per-request loop, warm-start `init=`, compile-once
behaviour, and the diagnostics flag.  (The sharded-placement path is covered
by tests/test_placement_mesh.py.)"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ddim_coeffs
from repro.core.parataa import sample as parataa_sample
from repro.sampling import (SampleRequest, SamplerSpec, SamplingEngine,
                            WarmStart, draw_noises, get_sampler,
                            register_sampler, run, sequential_sample)
from tests.helpers import make_label_denoiser, make_oracle_denoiser

D = 32
N_LABELS = 4


def make_engine(coeffs, spec, **kw):
    return SamplingEngine(make_label_denoiser(**kw), params=None,
                          coeffs=coeffs, spec=spec, sample_shape=(D,))


# --- spec registry ---------------------------------------------------------

def test_registry_resolution_and_overrides():
    taa = get_sampler("taa")
    assert taa.solver == "taa" and not taa.is_sequential
    fp = get_sampler("fp")
    assert fp.solver_config(30).order_k == 30      # FULL_ORDER resolves to T
    assert fp.solver_config(30).history_m == 1
    tuned = get_sampler("taa", order_k=4, s_max=7)
    assert tuned.solver_config(50).order_k == 4
    assert tuned.solver_config(50).s_max == 7
    assert get_sampler("taa").solver_config(50).s_max == 100  # 2*T heuristic
    with pytest.raises(KeyError):
        get_sampler("nope")
    with pytest.raises(ValueError):
        get_sampler("seq").solver_config(10)


def test_register_custom_sampler():
    register_sampler(SamplerSpec(name="taa-tight", solver="taa", tau=1e-4))
    assert get_sampler("taa-tight").tau == 1e-4


# --- engine ≡ per-request loop --------------------------------------------

def test_engine_batched_equals_per_request_loop():
    """Acceptance: a vmap-batched engine dispatch reproduces the old
    one-request-at-a-time loop on CPU, with identical iteration counts."""
    T = 15
    coeffs = ddim_coeffs(T)
    spec = get_sampler("taa")
    eng = make_engine(coeffs, spec)
    reqs = [SampleRequest(label=i % N_LABELS, seed=50 + i) for i in range(4)]
    results = eng.run_batch(reqs, batch_size=4)

    eps_apply = make_label_denoiser()
    solver = spec.solver_config(T)
    for req, res in zip(reqs, results):
        xi = draw_noises(jax.random.PRNGKey(req.seed), coeffs, (D,))

        def eps_fn(xw, taus, label=req.label):
            return eps_apply(None, xw, taus,
                             jnp.full((xw.shape[0],), label, jnp.int32))

        traj, info = parataa_sample(eps_fn, coeffs, solver, xi)
        # XLA:CPU (jax 0.9) compiles the vmapped batch-4 program and the
        # batch-1 loop differently, so f32 rounding differs and compounds
        # over the solve: observed max |diff| 2.1e-5 on trajectories of
        # max magnitude 3.6.  atol 1e-4 leaves ~5x headroom and is 10x
        # under the stopping tolerance tau=1e-3.
        np.testing.assert_allclose(
            np.asarray(res.trajectory), np.asarray(traj), rtol=0, atol=1e-4,
            err_msg=f"request {req} diverged from the per-request loop")
        assert res.iters == int(info["iters"])
        assert res.nfe == int(info["nfe"])
        assert res.converged


def test_engine_seq_spec_matches_reference():
    T = 12
    coeffs = ddim_coeffs(T)
    eng = make_engine(coeffs, get_sampler("seq"))
    reqs = [SampleRequest(label=i, seed=7 + i) for i in range(3)]
    results = eng.run_batch(reqs)
    eps_apply = make_label_denoiser()
    for req, res in zip(reqs, results):
        xi = draw_noises(jax.random.PRNGKey(req.seed), coeffs, (D,))

        def eps_fn(xw, taus, label=req.label):
            return eps_apply(None, xw, taus,
                             jnp.full((xw.shape[0],), label, jnp.int32))

        x_ref = sequential_sample(eps_fn, coeffs, xi)
        assert res.iters == T and res.nfe == T
        np.testing.assert_array_equal(np.asarray(res.x0), np.asarray(x_ref))


# --- warm starts -----------------------------------------------------------

def test_warm_start_init_converges_faster():
    """Sec 4.2 via the functional API: trajectory init + T_init beats cold."""
    coeffs = ddim_coeffs(50)
    eps1 = make_oracle_denoiser(D, seed=0)
    eps2 = make_oracle_denoiser(D, seed=0, nonlin=0.35)  # "similar prompt"
    xi = draw_noises(jax.random.PRNGKey(6), coeffs, (D,))
    spec = get_sampler("taa", s_max=300)
    res1 = run(spec, eps1, coeffs, xi)
    assert bool(res1.converged)
    cold = run(spec, eps2, coeffs, xi)
    warm = run(spec, eps2, coeffs, xi, init=WarmStart(res1.trajectory, 35))
    assert bool(warm.converged)
    assert int(warm.iters) <= int(cold.iters)
    assert int(warm.nfe) < int(cold.nfe)


def test_engine_mixed_cold_and_warm_batch():
    """Cold and warm requests share ONE compiled program (warm start is
    data: init trajectory + t_init scalar)."""
    T = 20
    coeffs = ddim_coeffs(T)
    spec = get_sampler("taa")
    eng = make_engine(coeffs, spec)
    seed_req = SampleRequest(label=1, seed=3)
    [solved] = eng.run_batch([seed_req])
    cold = SampleRequest(label=2, seed=3)
    warm = SampleRequest(label=2, seed=3,
                         init=WarmStart(solved.trajectory, t_init=12))
    res_cold, res_warm = eng.run_batch([cold, warm], batch_size=2)
    assert res_warm.converged and res_cold.converged
    assert res_warm.iters <= res_cold.iters
    # one trace for the B=1 seed batch, one for the B=2 mixed batch
    assert eng.stats["traces"] == 2


# --- compile-once + padding ------------------------------------------------

def test_engine_compiles_once_across_batches():
    coeffs = ddim_coeffs(10)
    eng = make_engine(coeffs, get_sampler("taa"))
    reqs = [SampleRequest(label=i % N_LABELS, seed=i) for i in range(5)]
    # 3 dispatches (2+2+1-padded) must reuse one compiled program
    results = eng.run_batch(reqs, batch_size=2)
    assert len(results) == 5
    assert eng.stats["batches"] == 3
    assert eng.stats["traces"] == 1
    eng.run_batch(reqs[:2], batch_size=2)
    assert eng.stats["traces"] == 1
    assert eng.throughput() > 0
    # padded tail request matches its unpadded execution
    [ref] = eng.run_batch([reqs[4]], batch_size=1)  # B=1: separate trace
    np.testing.assert_array_equal(np.asarray(results[4].x0),
                                  np.asarray(ref.x0))


# --- diagnostics flag ------------------------------------------------------

def test_diagnostics_flag_records_history():
    T = 20
    coeffs = ddim_coeffs(T)
    eps = make_oracle_denoiser(D)
    xi = draw_noises(jax.random.PRNGKey(8), coeffs, (D,))
    spec = get_sampler("taa", s_max=60)
    plain = run(spec, eps, coeffs, xi)
    rec = run(spec, eps, coeffs, xi, diagnostics=True)
    np.testing.assert_allclose(np.asarray(plain.trajectory),
                               np.asarray(rec.trajectory), atol=1e-5)
    assert int(plain.iters) == int(rec.iters)
    assert rec.diagnostics["res_history"].shape == (60, T)
    assert rec.diagnostics["x0_history"].shape == (60, D)
    # legacy info-dict view keeps the old keys
    assert "res_history" in rec.info and "iters" in rec.info
    # the sequential sampler has no solver iterations to record or warm-start
    with pytest.raises(ValueError):
        run(get_sampler("seq"), eps, coeffs, xi, diagnostics=True)
    with pytest.raises(ValueError):
        run(get_sampler("seq"), eps, coeffs, xi,
            init=WarmStart(plain.trajectory, 10))
    eng = make_engine(coeffs, get_sampler("seq"))
    with pytest.raises(ValueError):
        eng.run_batch([SampleRequest(seed=1)], diagnostics=True)
    with pytest.raises(ValueError):
        eng.run_batch([SampleRequest(seed=1,
                                     init=WarmStart(plain.trajectory, 10))])


# --- warm-start restart-depth semantics ------------------------------------

def test_warm_start_explicit_t_init_zero_is_fully_solved():
    """Regression: an explicit ``t_init=0`` (fully-solved warm start) must
    reach the solver as 0 — not be falsy-coerced into a cold start (T)."""
    T = 20
    coeffs = ddim_coeffs(T)
    eng = make_engine(coeffs, get_sampler("taa"))
    [solved] = eng.run_batch([SampleRequest(label=1, seed=5)])
    assert solved.converged and solved.iters > 1
    [verify] = eng.run_batch(
        [SampleRequest(label=1, seed=5,
                       init=WarmStart(solved.trajectory, t_init=0))])
    # the solver only verifies convergence of the already-solved trajectory:
    # one window pass, not a cold-start solve
    assert verify.converged
    assert verify.iters == 1
    np.testing.assert_allclose(np.asarray(verify.x0), np.asarray(solved.x0),
                               atol=1e-5)
    # default (t_init=None) stays a full restart with the trajectory as the
    # initial iterate — equivalent to the old cold-depth behaviour
    [full] = eng.run_batch(
        [SampleRequest(label=1, seed=5, init=WarmStart(solved.trajectory))])
    assert full.converged and full.iters >= verify.iters


@pytest.mark.parametrize("solver,dtype", [
    ("aa+", jnp.float32),
    ("taa", jnp.bfloat16),
    ("aa+", jnp.bfloat16),
])
def test_warm_start_from_result_resumes_bitwise(solver, dtype):
    """A draft's ``WarmStart.from_result`` handle re-``run`` through the
    unified API is pure plumbing: bitwise-equal to handing the solver core
    the same trajectory via ``x_init``/``t_init`` — under windowed (aa+)
    specs and bf16 trajectories, at full-restart (None), mid-depth, and
    verify-only (t_init=0) restart depths."""
    T = 20
    coeffs = ddim_coeffs(T)
    eps = make_oracle_denoiser(D, seed=3)
    xi = draw_noises(jax.random.PRNGKey(11), coeffs, (D,))
    # bf16 residuals floor far above f32's: give those cases a tolerance
    # closer to what the dtype can reach
    spec = get_sampler(solver,
                       tau=2e-2 if dtype == jnp.bfloat16 else 1e-3)
    cold = run(spec, eps, coeffs, xi, dtype=dtype)
    draft = run(spec, eps, coeffs, xi,
                request=SampleRequest(quality_steps=2), dtype=dtype)
    assert draft.early_stopped and not draft.converged
    # the draft trajectory keeps the solver dtype: warm starts hand it
    # back unconverted (the engine pack casts, not the handle)
    assert np.asarray(draft.trajectory).dtype == np.dtype(dtype)
    solver_cfg = spec.solver_config(T)
    for t in (None, T // 2, 0):
        ws = WarmStart.from_result(draft, t_init=t)
        assert ws.t_init == t
        resumed = run(spec, eps, coeffs, xi, init=ws, dtype=dtype)
        traj, info = parataa_sample(eps, coeffs, solver_cfg, xi,
                                    x_init=draft.trajectory, t_init=t,
                                    dtype=dtype)
        assert np.array_equal(np.asarray(resumed.trajectory),
                              np.asarray(traj)), \
            f"resume at t_init={t} diverged from the solver core"
        assert resumed.iters == int(info["iters"])
        assert resumed.nfe == int(info["nfe"])
        assert resumed.converged == bool(info["converged"])
        if t is None:
            # the refine tier's contract: a full-restart resume refines
            # the draft at least as far as a cold solve gets (triangular
            # AA in bf16 floors above tau on this oracle, so "converged"
            # is pinned to the cold solve rather than asserted outright)
            assert resumed.converged == cold.converged
            assert resumed.iters <= cold.iters


# --- per-request solver budgets (tau / max_iters / quality_steps) -----------

def test_per_request_tau_is_data_to_one_program():
    """A looser per-request tau retires that lane earlier INSIDE a shared
    dispatch, matches a solo run at the same tau bitwise, and defaults stay
    bitwise-identical to the no-override engine — all under one trace."""
    T = 20
    coeffs = ddim_coeffs(T)
    eng = make_engine(coeffs, get_sampler("taa"))
    default = SampleRequest(label=1, seed=5)
    loose = SampleRequest(label=2, seed=6, tau=5e-2)
    res_d, res_l = eng.run_batch([default, loose], batch_size=2)
    assert eng.stats["traces"] == 1
    assert res_l.iters <= res_d.iters
    # the loose lane == a solo engine whose SPEC carries that tau
    [solo] = make_engine(coeffs, get_sampler("taa", tau=5e-2)).run_batch(
        [SampleRequest(label=2, seed=6)])
    np.testing.assert_array_equal(np.asarray(res_l.trajectory),
                                  np.asarray(solo.trajectory))
    assert res_l.iters == solo.iters
    # the default lane == the pre-override engine output
    [ref] = make_engine(coeffs, get_sampler("taa")).run_batch(
        [SampleRequest(label=1, seed=5)])
    np.testing.assert_array_equal(np.asarray(res_d.trajectory),
                                  np.asarray(ref.trajectory))


def test_quality_steps_and_max_iters_early_exit():
    """Sec 4.1: a quality-steps budget returns the iterate at that
    iteration (early_stopped, not converged); max_iters behaves the same
    as a hard cap."""
    T = 20
    coeffs = ddim_coeffs(T)
    eng = make_engine(coeffs, get_sampler("taa"))
    [full] = eng.run_batch([SampleRequest(label=1, seed=5)])
    assert full.converged and not full.early_stopped
    [qs] = eng.run_batch([SampleRequest(label=1, seed=5, quality_steps=3)])
    assert qs.iters == 3 and qs.early_stopped and not qs.converged
    assert qs.nfe < full.nfe
    [mi] = eng.run_batch([SampleRequest(label=1, seed=5, max_iters=2)])
    assert mi.iters == 2 and mi.early_stopped
    # a budget ABOVE the convergence point changes nothing (bitwise)
    [roomy] = eng.run_batch(
        [SampleRequest(label=1, seed=5, max_iters=full.iters + 5)])
    assert roomy.converged and not roomy.early_stopped
    np.testing.assert_array_equal(np.asarray(roomy.trajectory),
                                  np.asarray(full.trajectory))


def test_seq_spec_rejects_solver_overrides():
    eng = make_engine(ddim_coeffs(10), get_sampler("seq"))
    with pytest.raises(ValueError, match="solver-iteration budgets"):
        eng.run_batch([SampleRequest(seed=1, tau=1e-2)])
    with pytest.raises(ValueError, match="solver-iteration budgets"):
        eng.run_batch([SampleRequest(seed=1, quality_steps=3)])


def test_functional_run_honors_request_budgets_like_the_engine():
    """Both entry points of the unified API resolve per-request budgets
    through the same spec helpers: ``run(request=...)`` early-exits at
    quality_steps exactly like ``engine.run_batch`` does, and seq rejects
    overrides on both."""
    T = 20
    coeffs = ddim_coeffs(T)
    eps = make_oracle_denoiser(D)
    xi = draw_noises(jax.random.PRNGKey(8), coeffs, (D,))
    spec = get_sampler("taa")
    req = SampleRequest(quality_steps=3)
    res = run(spec, eps, coeffs, xi, request=req)
    assert res.iters == 3 and res.early_stopped and not res.converged
    full = run(spec, eps, coeffs, xi)
    assert full.converged and not full.early_stopped
    loose = run(spec, eps, coeffs, xi, request=SampleRequest(tau=5e-2))
    assert loose.converged and loose.iters <= full.iters
    with pytest.raises(ValueError, match="solver-iteration budgets"):
        run(get_sampler("seq"), eps, coeffs, xi,
            request=SampleRequest(tau=1e-2))


# --- dispatch work accounting ------------------------------------------------

def test_dispatch_reports_per_lane_iters_and_wasted_frac():
    """The whole-batch dispatch report exposes per-lane iters/nfe and the
    wasted-lane-iteration fraction (work burned past each lane's own
    convergence — what iteration-level batching reclaims)."""
    T = 20
    coeffs = ddim_coeffs(T)
    eng = make_engine(coeffs, get_sampler("taa"))
    reqs = [SampleRequest(label=1, seed=5),
            SampleRequest(label=2, seed=6, quality_steps=2)]
    results = eng.run_batch(reqs, batch_size=2)
    [report] = eng.last_dispatches
    assert report["iters"] == [r.iters for r in results]
    assert report["nfe"] == [r.nfe for r in results]
    assert report["device_iters"] == max(r.iters for r in results)
    # the quality-capped lane idled while the slow lane ran to tolerance
    expected = 1.0 - sum(r.iters for r in results) \
        / (report["device_iters"] * 2)
    assert report["wasted_iter_frac"] == pytest.approx(expected)
    assert report["device_nfe"] == report["device_iters"] * 2 * eng.window


# --- stepwise host protocol (device-resident serving hot path) ---------------

def _drain_bank(eng, bank):
    """Drive a bank until every lane retires; returns [(lane, result)...]."""
    out = []
    guard = 0
    while any(r is not None for r in bank.requests):
        eng.stepwise_step(bank)
        out.extend(eng.stepwise_harvest(bank))
        guard += 1
        assert guard < 1000
    return out


def test_stepwise_harvest_gathers_only_retired_lanes():
    """Tentpole acceptance: harvest fetches len(ready) x (T+1) x D rows via
    the compiled-once gather program, not the slots-wide bank, and the
    whole protocol compiles exactly FIVE stepwise programs."""
    T = 16
    eng = make_engine(ddim_coeffs(T), get_sampler("taa"))
    bank = eng.stepwise_open(4, chunk_iters=1)
    # one lane retires long before the rest: quality_steps=1 vs tolerance
    reqs = [SampleRequest(label=0, seed=1, quality_steps=1)] + \
        [SampleRequest(label=i % N_LABELS, seed=2 + i) for i in range(3)]
    eng.stepwise_refill(bank, [0, 1, 2, 3], reqs)
    eng.stepwise_step(bank)
    mark = bank.host_fetch_bytes
    [(lane, res)] = eng.stepwise_harvest(bank)
    assert lane == 0 and res.early_stopped and res.iters == 1
    lane_bytes = (T + 1) * D * 4
    fetched = bank.host_fetch_bytes - mark
    # ONE retired lane's trajectory + its residual row + the (slots, 5)
    # packed poll (incl. its piggybacked residual column) — nowhere near
    # the full 4-lane bank
    assert fetched == lane_bytes + T * 4 + bank.slots * 5 * 4
    assert bank.gather_launches == 1 and bank.harvests == 1
    full_bank = bank.slots * (lane_bytes + T * 4)
    assert fetched < full_bank / 2
    # harvested trajectory matches the lane's own solo solve bitwise
    [solo] = make_engine(ddim_coeffs(T), get_sampler("taa")).run_batch(
        [reqs[0]])
    np.testing.assert_array_equal(np.asarray(res.trajectory),
                                  np.asarray(solo.trajectory))
    _drain_bank(eng, bank)
    assert eng.stats["stepwise_traces"] == 5   # open/init/merge/step/gather
    assert eng.stats["gather_launches"] == bank.gather_launches


def test_stepwise_poll_piggybacked_cached_and_invalidated():
    """One blocking poll per round: the step program's packed (slots, 5)
    summary is fetched once, harvest/report share the cached copy, and
    step/refill invalidate it."""
    T = 12
    eng = make_engine(ddim_coeffs(T), get_sampler("taa"))
    bank = eng.stepwise_open(2, chunk_iters=2)
    eng.stepwise_refill(bank, [0, 1],
                        [SampleRequest(label=0, seed=3, quality_steps=2),
                         SampleRequest(label=1, seed=4)])
    eng.stepwise_step(bank)
    assert bank.summary is not None and bank.poll_cache is None
    polls0 = bank.blocking_polls
    polled = eng.stepwise_poll(bank)
    assert bank.blocking_polls == polls0 + 1
    # second poll, harvest, and report all reuse the round's cache
    assert eng.stepwise_poll(bank) is polled
    harvested = eng.stepwise_harvest(bank)
    eng.stepwise_report(bank)
    assert bank.blocking_polls == polls0 + 1
    assert [lane for lane, _ in harvested] == [0]
    # stepping invalidates: the NEXT round pays exactly one fresh poll
    eng.stepwise_step(bank)
    assert bank.poll_cache is None
    eng.stepwise_poll(bank)
    assert bank.blocking_polls == polls0 + 2
    # refill drops the stale pre-merge summary: the refilled lane must not
    # look finished to the next poll
    eng.stepwise_refill(bank, [0], [SampleRequest(label=2, seed=5)])
    assert bank.summary is None and bank.poll_cache is None
    polled = eng.stepwise_poll(bank)
    assert not polled["finished"][0] and polled["iters"][0] == 0
    _drain_bank(eng, bank)


def test_stepwise_seq_spec_skips_residual_fetch():
    """Sequential specs discard residuals: their gather program never
    fetches r_last, and the harvested results carry residuals=None."""
    T = 8
    eng = make_engine(ddim_coeffs(T), get_sampler("seq"))
    bank = eng.stepwise_open(2, chunk_iters=T)
    eng.stepwise_refill(bank, [0, 1], [SampleRequest(label=0, seed=7),
                                       SampleRequest(label=1, seed=8)])
    eng.stepwise_step(bank)
    mark = bank.host_fetch_bytes
    results = eng.stepwise_harvest(bank)
    assert len(results) == 2
    assert all(res.residuals is None for _, res in results)
    fetched = bank.host_fetch_bytes - mark
    # 2 lanes' trajectories + packed poll; NO T x 4 residual rows
    assert fetched == 2 * (T + 1) * D * 4 + bank.slots * 5 * 4
    # a taa engine at the same geometry DOES fetch its residual rows
    eng2 = make_engine(ddim_coeffs(T), get_sampler("taa"))
    bank2 = eng2.stepwise_open(2, chunk_iters=2)
    eng2.stepwise_refill(bank2, [0], [SampleRequest(label=0, seed=7,
                                                    quality_steps=2)])
    eng2.stepwise_step(bank2)
    mark2 = bank2.host_fetch_bytes
    [(_, res2)] = eng2.stepwise_harvest(bank2)
    assert res2.residuals is not None and res2.residuals.shape == (T,)
    assert bank2.host_fetch_bytes - mark2 == \
        (T + 1) * D * 4 + T * 4 + bank2.slots * 5 * 4


def test_stepwise_report_and_stats_expose_protocol_counters():
    """stepwise_report and engine stats carry the host-protocol counters
    (host_fetch_bytes / blocking_polls / gather_launches / harvests)."""
    eng = make_engine(ddim_coeffs(10), get_sampler("taa"))
    bank = eng.stepwise_open(2, chunk_iters=3)
    eng.stepwise_refill(bank, [0, 1], [SampleRequest(label=0, seed=9),
                                       SampleRequest(label=1, seed=10)])
    _drain_bank(eng, bank)
    report = eng.stepwise_report(bank)
    for key in ("host_fetch_bytes", "blocking_polls", "gather_launches",
                "harvests"):
        assert report[key] == getattr(bank, key) > 0
    for key in ("host_fetch_bytes", "blocking_polls", "gather_launches"):
        assert eng.stats[key] >= report[key]
    # whole-batch collect also accounts its one fetch per dispatch
    eng2 = make_engine(ddim_coeffs(10), get_sampler("taa"))
    eng2.run_batch([SampleRequest(label=1, seed=11)])
    assert eng2.stats["blocking_polls"] == 1
    [d] = eng2.last_dispatches
    assert d["blocking_polls"] == 1
    assert d["host_fetch_bytes"] == eng2.stats["host_fetch_bytes"] > 0


def test_update_launches_counted_per_round_and_cut_by_fuse_round():
    """The launch-accounting tentpole: every dispatch and stepwise round
    counts the modeled Anderson-update launches (3/iter staged, 1/iter
    fused, 0 when no update runs), surfaces them in last_dispatches /
    stepwise_report / stats, and fuse_round cuts them 3x while keeping
    the outputs bitwise-identical on the CPU default routing."""
    T = 15
    coeffs = ddim_coeffs(T)
    staged = make_engine(coeffs, get_sampler("taa"))
    fused = make_engine(coeffs, get_sampler("taa", fuse_round=True))
    assert staged.update_launches_per_iter() == 3
    assert fused.update_launches_per_iter() == 1
    assert make_engine(coeffs, get_sampler("seq")).update_launches_per_iter() == 0
    assert make_engine(coeffs, get_sampler("fp")).update_launches_per_iter() == 0

    reqs = [SampleRequest(label=i % N_LABELS, seed=60 + i) for i in range(3)]
    res_s = staged.run_batch(reqs, batch_size=3)
    res_f = fused.run_batch(reqs, batch_size=3)
    for a, b in zip(res_s, res_f):
        np.testing.assert_array_equal(np.asarray(a.trajectory),
                                      np.asarray(b.trajectory))
        assert a.iters == b.iters
    [d_s] = staged.last_dispatches
    [d_f] = fused.last_dispatches
    assert d_s["update_launches"] == d_s["device_iters"] * 3
    assert d_f["update_launches"] == d_f["device_iters"] * 1
    assert d_s["update_launches"] == 3 * d_f["update_launches"]
    assert staged.stats["update_launches"] == d_s["update_launches"]
    assert fused.stats["update_launches"] == d_f["update_launches"]

    # stepwise drain: per-bank counter, surfaced in the report
    for eng, per_iter in ((staged, 3), (fused, 1)):
        eng.reset_stats()
        bank = eng.stepwise_open(2, chunk_iters=2)
        eng.stepwise_refill(bank, [0, 1],
                            [SampleRequest(label=0, seed=70),
                             SampleRequest(label=1, seed=71)])
        _drain_bank(eng, bank)
        report = eng.stepwise_report(bank)
        assert report["update_launches"] == bank.update_launches > 0
        assert bank.update_launches == bank.device_iters * per_iter
        assert eng.stats["update_launches"] == bank.update_launches
        assert eng.stats["stepwise_traces"] == 5  # protocol unchanged


# --- warm-start handles ------------------------------------------------------

def test_result_exposes_warm_start_handle():
    eng = make_engine(ddim_coeffs(15), get_sampler("taa"))
    [res] = eng.run_batch([SampleRequest(label=1, seed=4)])
    ws = res.warm_start(t_init=7)
    assert ws.t_init == 7 and ws.trajectory is res.trajectory
    assert WarmStart.from_result(res).t_init is None
    [again] = eng.run_batch([SampleRequest(label=1, seed=4, init=ws)])
    assert again.converged and again.iters <= res.iters


# --- deprecation shims are gone --------------------------------------------

def test_pr1_shims_removed():
    """The PR-1 deprecation shims were dropped once no caller remained; the
    canonical entry points are warning-free."""
    import repro.core as core
    import repro.diffusion.samplers as samplers
    assert not hasattr(core, "sample")
    assert not hasattr(core, "sample_recording")
    assert not hasattr(samplers, "sequential_sample")

    coeffs = ddim_coeffs(10)
    eps = make_oracle_denoiser(D)
    xi = draw_noises(jax.random.PRNGKey(2), coeffs, (D,))
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        run(get_sampler("taa"), eps, coeffs, xi)
        sequential_sample(eps, coeffs, xi)
