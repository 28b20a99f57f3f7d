"""`repro.obs` coverage: the metrics registry and StatsView bridge, the
span tracer's Chrome-trace export and profiler sink, the compile counters,
the named scopes of the step program, the convergence recorder, the engine's
injectable monotonic clock, and the serving-stack integration — traced
drains must leave every ticket a complete span chain plus a residual
curve while changing nothing about the solves or the host protocol
(`tools/stepwise_guard.py --phase obs` enforces the protocol half in CI;
these tests cover the semantics)."""
import glob
import json
import math
import os
import re

import numpy as np
import pytest

from repro.core import ddim_coeffs
from repro.obs import (ConvergenceRecorder, MetricsRegistry, Observability,
                       SpanTracer, StatsView, compile_totals, count_compiles,
                       json_safe)
from repro.sampling import SampleRequest, SamplingEngine, get_sampler
from repro.serving import (Batcher, BatchingPolicy, EngineKey, EngineRegistry,
                           RefinePlanner, RefinePolicy, RequestQueue,
                           ServingLoop)
from tests.helpers import make_label_denoiser

D = 24
N_LABELS = 4


def make_factory(**engine_kw):
    eps_apply = make_label_denoiser(dim=D, n_labels=N_LABELS)

    def factory(key):
        return SamplingEngine(eps_apply, None, ddim_coeffs(key.T),
                              get_sampler(key.solver), sample_shape=(D,),
                              **engine_kw)

    return factory


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


# --- metrics registry -------------------------------------------------------


def test_counter_gauge_histogram_with_labels():
    reg = MetricsRegistry()
    reg.counter("served").inc()
    reg.counter("served").inc(2, key="a")
    assert reg.counter("served").value() == 1
    assert reg.counter("served").value(key="a") == 2
    with pytest.raises(ValueError):
        reg.counter("served").inc(-1)

    reg.gauge("depth").set(4)
    reg.gauge("depth").add(-1)
    assert reg.gauge("depth").value() == 3

    h = reg.histogram("wait_s")
    for v in (0.01, 0.02, 0.02, 5.0):
        h.observe(v, key="a")
    s = h.summary(key="a")
    assert s["count"] == 4 and s["min"] == 0.01 and s["max"] == 5.0
    assert 0.01 <= s["p50"] <= 0.03
    assert h.summary() is None               # unlabeled series: no data
    assert h.percentile(0.5) is None
    # merged() aggregates across label sets
    h.observe(0.02, key="b")
    m = h.merged()
    assert m["count"] == 5 and m["max"] == 5.0

    # re-registering a name under a different type is an error
    with pytest.raises(ValueError):
        reg.gauge("served")


def test_snapshot_and_delta():
    reg = MetricsRegistry()
    reg.counter("n").inc(3)
    reg.histogram("h").observe(1.0)
    before = reg.snapshot()
    reg.counter("n").inc(2)
    reg.histogram("h").observe(2.0)
    after = reg.snapshot()
    # a snapshot is a copy: later updates leave it as it was
    assert before["n"][""] == 3 and before["h"][""]["count"] == 1
    assert after["n"][""] == 5
    assert after["h"][""]["count"] == 2 and after["h"][""]["sum"] == 3.0
    reg.counter("new").inc(7)
    assert "new" not in after and reg.snapshot()["new"][""] == 7


def test_stats_view_is_a_dict_and_mirrors_into_gauges():
    reg = MetricsRegistry()
    stats = StatsView(reg, "engine", labels={"engine": "k"},
                      initial={"batches": 0, "wall_s": 0.0})
    stats["batches"] += 2
    stats.update(requests=5)
    stats.setdefault("polls", 0)
    # dict semantics intact: equality, json, iteration
    assert stats == {"batches": 2, "wall_s": 0.0, "requests": 5, "polls": 0}
    assert json.loads(json.dumps(stats)) == stats
    # every write mirrored into a labeled gauge
    assert reg.gauge("engine.batches").value(engine="k") == 2
    assert reg.gauge("engine.requests").value(engine="k") == 5
    # rebind replays current values onto a shared registry
    shared = MetricsRegistry()
    stats.rebind(shared, labels={"engine": "k2"})
    assert shared.gauge("engine.batches").value(engine="k2") == 2
    stats["batches"] += 1
    assert shared.gauge("engine.batches").value(engine="k2") == 3
    assert reg.gauge("engine.batches").value(engine="k") == 2  # old detached


# --- span tracer ------------------------------------------------------------


def test_tracer_spans_export_strict_json(tmp_path):
    clock = FakeClock(10.0)
    tracer = SpanTracer(enabled=True, clock=clock)
    clock.t = 10.5
    with tracer.span("work", tid="engine-a", n=3):
        clock.t = 11.0
    tracer.async_begin("ticket", 7, key="k", ts_s=10.2, bad=float("nan"))
    tracer.async_begin("ticket", 7)            # idempotent: no double-open
    tracer.async_instant("admit", 7)
    tracer.async_end("ticket", 7, residual_curve=[
        dict(round=0, residual=np.float32(0.5)),
        dict(round=1, residual=float("inf"))])
    events = tracer.events()
    assert [e["ph"] for e in events] == ["X", "b", "n", "e"]
    span = events[0]
    assert span["ts"] == pytest.approx(0.5e6) \
        and span["dur"] == pytest.approx(0.5e6)
    # ts_s backdating + non-finite arg sanitization (strict JSON)
    assert events[1]["ts"] == pytest.approx(0.2e6)
    assert events[1]["args"]["bad"] is None
    curve = events[3]["args"]["residual_curve"]
    assert curve[0]["residual"] == 0.5 and curve[1]["residual"] is None

    path = tracer.export(tmp_path / "t.json")
    payload = json.loads(path.read_text())    # strict JSON round-trips
    assert len(payload["traceEvents"]) == len(events) + 2  # +thread names
    threads = {e["args"]["name"] for e in payload["traceEvents"]
               if e.get("ph") == "M"}
    assert threads == {"engine-a", "ticket"}


def test_tracer_disabled_and_bounded():
    off = SpanTracer(enabled=False)
    with off.span("x"):
        pass
    off.async_begin("t", 1)
    assert off.events() == []

    small = SpanTracer(enabled=True, max_events=2)
    for i in range(5):
        small.instant(f"e{i}")
    assert len(small.events()) == 2 and small.dropped == 3


def test_json_safe_coercions():
    assert json_safe({"a": np.int32(3), "b": (np.float64(1.5),)}) \
        == {"a": 3, "b": [1.5]}
    assert json_safe(float("-inf")) is None
    assert json_safe(np.array([1.0, float("nan")])) == [1.0, None]


# --- observability bundle + convergence recorder ----------------------------


def test_observability_bundle_modes():
    off = Observability.off()
    assert not off.active and not off.tracer.enabled
    on = Observability.enabled()
    assert on.active and on.tracer.enabled
    # off() instances each get a private registry: no cross-talk
    a, b = Observability.off(), Observability.off()
    a.metrics.counter("n").inc()
    assert b.metrics.counter("n").value() == 0


class _T:
    def __init__(self, seqno):
        self.seqno = seqno
        self.residual_curve = None


def test_convergence_recorder_accumulates_and_finishes():
    reg = MetricsRegistry()
    rec = ConvergenceRecorder(reg)
    t0, t1 = _T(0), _T(1)
    polled = dict(iters=np.array([2, 2]),
                  residual=np.array([0.5, np.inf], np.float32))
    rec.observe_round("k", 0, [(0, t0), (1, t1)], polled)
    polled2 = dict(iters=np.array([4, 4]),
                   residual=np.array([0.1, np.inf], np.float32))
    rec.observe_round("k", 1, [(0, t0), (1, None)], polled2)
    assert rec.open_curves() == 2

    curve = rec.finish(t0)
    assert t0.residual_curve == curve
    assert [p["residual"] for p in curve] == [0.5, pytest.approx(0.1)]
    assert [p["iters"] for p in curve] == [2, 4]
    assert reg.histogram("convergence.rounds_to_retire").summary()["count"] \
        == 1
    # +inf polls (seq/fresh lanes) become residual=None, not a histogram hit
    seq_curve = rec.finish(t1)
    assert [p["residual"] for p in seq_curve] == [None]
    assert reg.histogram("convergence.final_residual").summary()["count"] == 1

    rec.observe_round("k", 2, [(0, _T(9))], polled)
    rec.discard(_T(9))
    assert rec.open_curves() == 0


# --- engine: injectable clock, report capping, reset_stats ------------------


def test_engine_clock_injection_times_dispatch_wall():
    clock = FakeClock(50.0)
    engine = make_factory(clock=clock)(EngineKey("oracle", 6, "taa"))
    pending = engine.dispatch([SampleRequest(label=1, seed=1)], slots=1)
    clock.t = 53.5
    engine.collect(pending)
    assert engine.stats["wall_s"] == pytest.approx(3.5)
    assert engine.last_dispatches[-1]["wall_s"] == pytest.approx(3.5)


def test_last_dispatches_capped_at_max_reports():
    engine = make_factory()(EngineKey("oracle", 6, "taa"))
    engine.MAX_DISPATCH_REPORTS = 3
    engine.run_batch([SampleRequest(label=i % N_LABELS, seed=i)
                      for i in range(5)], batch_size=1)
    assert engine.stats["batches"] == 5
    assert len(engine.last_dispatches) == 3
    assert len(engine.last_batch_walls) == 3


def test_reset_stats_rewinds_every_counter_but_traces():
    key = EngineKey("oracle", 8, "taa")
    registry = EngineRegistry(make_factory())
    queue = RequestQueue()
    loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=2)),
                       chunk_iters=2)
    tickets = [queue.submit(SampleRequest(label=i % N_LABELS, seed=30 + i),
                            key) for i in range(4)]
    loop.drain()
    [t.result(timeout=0) for t in tickets]
    engine = registry.get(key)
    # the drain populated the protocol counters; reset rewinds them ALL
    assert engine.stats["blocking_polls"] > 0
    assert engine.stats["host_fetch_bytes"] > 0
    assert engine.stats["gather_launches"] > 0
    traces = engine.stats["traces"]
    steptraces = engine.stats["stepwise_traces"]
    assert steptraces == 5
    view = engine.stats
    engine.reset_stats()
    assert engine.stats is view               # identity kept (it's a view)
    for k, v in engine.stats.items():
        if k in ("traces", "stepwise_traces"):
            continue
        assert v == 0, f"reset_stats left {k}={v}"
    assert engine.stats["traces"] == traces
    assert engine.stats["stepwise_traces"] == steptraces
    # the registry mirror followed the rewind
    assert engine.obs.metrics.gauge("engine.blocking_polls").value(
        engine=engine.name) == 0


def test_bank_reports_shape_after_preemption():
    """After refine-lane preemptions the bank report stays per-slot shaped:
    residual/warm_start_depth have one entry per lane, vacated lanes report
    None, and the protocol counters survive the vacate/refill churn."""
    key = EngineKey("oracle", 16, "taa")
    registry = EngineRegistry(make_factory())
    queue = RequestQueue()
    loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=2)),
                       chunk_iters=1, refiner=RefinePlanner(RefinePolicy()))
    draft_tix = [queue.submit(SampleRequest(label=i, seed=10 + i,
                                            quality_steps=1), key)
                 for i in range(2)]
    for _ in range(50):
        loop.pump(flush=True)
        if all(t.draft_done() for t in draft_tix) \
                and queue.pending(key) == 0 and loop.inflight == 2:
            break
    else:
        pytest.fail("refine continuations never occupied the lanes")
    urgent = [queue.submit(SampleRequest(label=2 + i, seed=20 + i), key)
              for i in range(2)]
    loop.pump(flush=True)
    assert loop.stats["preemptions"] >= 1
    loop.drain()
    for t in draft_tix + urgent:
        assert t.result(timeout=0).converged

    report = loop.bank_reports()[key]
    assert len(report["residual"]) == report["slots"]
    assert len(report["warm_start_depth"]) == report["slots"]
    assert all(r is None for r in report["residual"])    # drained: all empty
    # bank completions count LANE retirements (draft exits + refine
    # continuations), not tickets — ticket completions live on the loop
    assert report["completed"] >= 4
    assert loop.stats["completed"] == 4
    assert report["blocking_polls"] > 0
    assert report["host_fetch_bytes"] > 0
    assert registry.get(key).stats["stepwise_traces"] == 5


# --- serving-stack integration ----------------------------------------------


def test_traced_stepwise_drain_spans_curves_and_metrics(tmp_path):
    """One enabled Observability wired through queue + loop: every resolved
    ticket carries a complete submit -> resolve span chain and a non-empty
    residual curve, the loop/queue metrics agree with the stats dicts, and
    the export is a loadable trace."""
    key = EngineKey("oracle", 12, "taa")
    registry = EngineRegistry(make_factory())
    obs = Observability.enabled()
    queue = RequestQueue(obs=obs)
    loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=4)),
                       chunk_iters=2, obs=obs)
    tickets = [queue.submit(
        SampleRequest(label=i % N_LABELS, seed=50 + i,
                      **({} if i % 2 == 0 else dict(quality_steps=2))), key)
        for i in range(6)]
    loop.drain()
    for t in tickets:
        t.result(timeout=0)
        assert t.residual_curve, f"ticket #{t.seqno} has no residual curve"
        finite = [p["residual"] for p in t.residual_curve
                  if p["residual"] is not None]
        assert finite, f"ticket #{t.seqno} curve has no finite residuals"
    assert obs.convergence.open_curves() == 0

    events = obs.tracer.events()
    begins = {e["id"] for e in events if e["ph"] == "b"}
    ends = {e["id"] for e in events if e["ph"] == "e"}
    marks = {}
    for e in events:
        if e["ph"] == "n":
            marks.setdefault(e["id"], set()).add(e["name"])
    for t in tickets:
        ident = str(t.seqno)
        assert ident in begins and ident in ends
        assert marks[ident] & {"admit", "splice"}
    # engine spans rode the engine's own track
    span_names = {e["name"] for e in events if e["ph"] == "X"}
    assert {"stepwise.open", "stepwise.step", "stepwise.poll",
            "stepwise.harvest"} <= span_names

    # metrics: one registry spans queue, loop, and engine
    assert obs.metrics.counter("queue.submitted").value(
        key=key.describe()) == 6
    assert obs.metrics.gauge("loop.completed").value() == 6
    assert obs.metrics.histogram("loop.queue_wait_s").merged()["count"] == 6
    assert obs.metrics.gauge("engine.stepwise_traces").value(
        engine=key.describe()) == 5

    payload = json.loads(obs.tracer.export(tmp_path / "t.json").read_text())
    assert payload["traceEvents"]


def test_failed_ticket_closes_span_and_discards_curve():
    key = EngineKey("oracle", 8, "taa")
    registry = EngineRegistry(make_factory())
    obs = Observability.enabled()

    def reject(request, key):
        raise ValueError("bad request")

    queue = RequestQueue(validate=reject, obs=obs)
    ticket = queue.submit(SampleRequest(label=1, seed=1), key)
    with pytest.raises(ValueError):
        ticket.result(timeout=0)
    events = obs.tracer.events()
    end = [e for e in events if e["ph"] == "e"]
    assert len(end) == 1 and "bad request" in end[0]["args"]["error"]
    assert obs.metrics.counter("queue.rejected").value(
        key=key.describe()) == 1
    assert obs.convergence.open_curves() == 0


# --- profiler sink, compile counters, named scopes ---------------------------


def _profiler_host_events(trace_dir) -> set:
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    profile = ProfileData.from_file(path)
    return {e.name for plane in profile.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events}


@pytest.mark.parametrize("enabled", [False, True])
def test_span_lands_on_the_profiler_host_plane(tmp_path, enabled):
    """Inside a profiler session every span reaches the /host:CPU plane
    under its own name; only an enabled tracer also keeps a JSON event."""
    import jax
    tracer = SpanTracer(enabled=enabled)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("stepwise.poll", tid="engine-a", occupied=3):
            pass
    finally:
        jax.profiler.stop_trace()
    assert "stepwise.poll" in _profiler_host_events(str(tmp_path))
    assert [e["name"] for e in tracer.events()] == \
        (["stepwise.poll"] if enabled else [])


def test_compile_counters_name_the_function():
    import jax
    import jax.numpy as jnp
    reg = count_compiles(MetricsRegistry())
    count_compiles(reg)                 # a second call adds no listener
    before = compile_totals(reg)

    def obs_counted_fn(x):
        return x * 3 + 1

    jax.jit(obs_counted_fn)(jnp.arange(3.0))
    compiles = reg.counter("jax.compiles")
    assert compiles.value(fun_name="jit(obs_counted_fn)") == 1
    assert reg.counter("jax.traces").value(fun_name="obs_counted_fn") == 1
    after = compile_totals(reg)
    assert after["jax.compiles"] - before["jax.compiles"] >= 1
    assert set(reg.snapshot()) >= {"jax.traces", "jax.compiles",
                                   "jax.cache_hits"}


@pytest.mark.parametrize("solver,scopes", [
    ("taa", ("dit/attn", "dit/mlp", "dit/ada", "dit/weights",
             "parataa/denoise", "parataa/residual", "parataa/anderson")),
    ("seq", ("dit/attn", "dit/mlp", "parataa/denoise")),
])
def test_step_program_names_its_scopes(solver, scopes):
    """The stepwise step program's compiled HLO carries the DiT's and the
    solver's named scopes in its op_name metadata, which is what a device
    trace attributes op time by."""
    import jax
    from repro.configs.registry import ARCHS
    from repro.diffusion import dit
    from repro.launch import serve
    cfg = ARCHS["dit-xl"].reduced()
    params = dit.dit_init(cfg, jax.random.PRNGKey(0))
    engine = serve.make_engine(params, cfg, ddim_coeffs(4),
                               get_sampler(solver))
    bank = engine.stepwise_open(2, chunk_iters=1)
    text = engine._stepwise_program("step", 1).lower(
        engine.params, bank.state, bank.conds).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in scopes:
        assert any(f"/{scope}/" in n for n in names), scope
    # the DiT runs inside the solver's denoise scope
    assert any("/parataa/denoise/dit/attn/" in n for n in names)

