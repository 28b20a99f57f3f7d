"""`repro.serving` coverage: queue/ticket semantics, lazy engine registry,
batching policy (fill / deadline / flush / work-conserving, including
iteration-level ``plan_refill`` admission), the double-buffered serving
loop, the stepwise (``chunk_iters``) loop with mid-solve retire/refill, and
the per-key trajectory cache — async-served results must be bitwise-equal
to ``engine.run_batch`` over the same requests (host placement here,
8-device mesh in the subprocess variants), with mixed-key requests routed
to the right engine FIFO-fair per key."""
import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import ddim_coeffs
from repro.sampling import (SampleRequest, SamplingEngine, WarmStart,
                            get_sampler)
from repro.sampling.engine import PendingBatch
from repro.serving import (Batcher, BatchingPolicy, EngineKey, EngineRegistry,
                           RefinePlanner, RefinePolicy, RequestQueue,
                           ServingLoop, TrajectoryCache)
from tests.helpers import make_label_denoiser

D = 24
N_LABELS = 4


def make_factory(counts=None, **engine_kw):
    eps_apply = make_label_denoiser(dim=D, n_labels=N_LABELS)

    def factory(key):
        if counts is not None:
            counts[key] = counts.get(key, 0) + 1
        spec = get_sampler(key.solver)
        return SamplingEngine(eps_apply, None, ddim_coeffs(key.T), spec,
                              sample_shape=(D,), **engine_kw)

    return factory


def reference_engine(T, solver="taa"):
    return SamplingEngine(make_label_denoiser(dim=D, n_labels=N_LABELS),
                          None, ddim_coeffs(T), get_sampler(solver),
                          sample_shape=(D,))


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# --- queue + tickets --------------------------------------------------------

def test_queue_stamps_arrival_and_orders_by_priority():
    clock = FakeClock(100.0)
    q = RequestQueue(clock=clock)
    key = EngineKey("oracle", 10, "taa")
    t_lo1 = q.submit(SampleRequest(seed=1), key)
    clock.t = 101.0
    t_lo2 = q.submit(SampleRequest(seed=2), key)
    t_hi = q.submit(SampleRequest(seed=3, priority=5), key)
    # arrival stamped with the queue clock; explicit stamps are preserved
    assert t_lo1.request.arrival_time == 100.0
    assert t_lo2.request.arrival_time == 101.0
    pre = q.submit(SampleRequest(seed=4, arrival_time=42.0), key)
    assert pre.request.arrival_time == 42.0
    assert q.oldest_arrival(key) == 42.0
    assert len(q) == 4 and q.pending(key) == 4 and q.keys() == [key]
    # pop order: priority desc, FIFO among equals
    seeds = [t.request.seed for t in q.pop(key, 4)]
    assert seeds == [3, 1, 2, 4]
    assert q.pending(key) == 0 and q.keys() == []


def test_deadline_promotes_starved_low_priority_requests():
    """A low-priority ticket past the batching deadline jumps the priority
    order — sustained high-priority traffic must not starve it forever."""
    clock = FakeClock(0.0)
    q = RequestQueue(clock=clock)
    key = EngineKey("oracle", 10, "taa")
    old_low = q.submit(SampleRequest(seed=1, priority=0), key)
    clock.t = 100.0
    for seed in range(2, 6):
        q.submit(SampleRequest(seed=seed, priority=5), key)
    # without promotion the 4 priority-5 tickets would fill a 4-slot pop
    taken = q.pop(key, 4, promote_before=50.0)
    assert taken[0] is old_low                 # overdue ticket leads
    assert [t.request.seed for t in taken] == [1, 2, 3, 4]
    # the remainder keeps the (priority desc, seqno) invariant
    assert [t.request.seed for t in q.pop(key, 4)] == [5]


def test_ticket_result_blocks_fails_and_reports_latency():
    clock = FakeClock(10.0)
    q = RequestQueue(clock=clock)
    key = EngineKey("oracle", 10, "taa")
    ticket = q.submit(SampleRequest(seed=1), key)
    assert not ticket.done() and ticket.latency_s is None
    with pytest.raises(TimeoutError):
        ticket.result(timeout=0.01)
    clock.t = 13.5
    ticket.resolve("result")
    assert ticket.done() and ticket.result() == "result"
    assert ticket.latency_s == pytest.approx(3.5)
    failed = q.submit(SampleRequest(seed=2), key)
    failed.fail(RuntimeError("boom"))
    with pytest.raises(RuntimeError, match="boom"):
        failed.result()
    # a closed queue (dead serving loop) fails new submits immediately
    # instead of stranding them until their result() timeout
    q.close(RuntimeError("loop died"))
    stranded = q.submit(SampleRequest(seed=3), key)
    assert stranded.done() and q.pending(key) == 2  # not enqueued
    with pytest.raises(RuntimeError, match="loop died"):
        stranded.result()


# --- registry ---------------------------------------------------------------

def test_registry_constructs_each_key_lazily_once():
    counts = {}
    registry = EngineRegistry(make_factory(counts))
    k1 = EngineKey("oracle", 8, "taa")
    k2 = EngineKey("oracle", 8, "fp")
    assert len(registry) == 0 and k1 not in registry
    engine = registry.get(k1)
    assert registry.get(k1) is engine          # cached, not rebuilt
    assert counts == {k1: 1}
    registry.get(k2)
    assert counts == {k1: 1, k2: 1} and len(registry) == 2
    assert set(registry.engines()) == {k1, k2}
    assert "oracle/T8/taa" in registry.describe()


def test_registry_warmup_compiles_without_polluting_stats():
    registry = EngineRegistry(make_factory())
    key = EngineKey("oracle", 8, "taa")
    engine = registry.warmup(key, slots=4)
    assert engine.stats["traces"] == 1         # genuinely compiled
    assert engine.stats["batches"] == 0 and engine.stats["requests"] == 0
    assert engine.last_dispatches == []
    engine.run_batch([SampleRequest(seed=5)] * 4, batch_size=4)
    assert engine.stats["traces"] == 1         # warmed geometry reused


# --- batching policy --------------------------------------------------------

def test_batcher_fill_deadline_flush_and_fixed_slots():
    clock = FakeClock(0.0)
    q = RequestQueue(clock=clock)
    registry = EngineRegistry(make_factory())
    key = EngineKey("oracle", 8, "taa")
    policy = BatchingPolicy(max_batch=4, max_wait_s=10.0,
                            work_conserving=False)
    batcher = Batcher(policy)

    q.submit(SampleRequest(seed=1), key)
    q.submit(SampleRequest(seed=2), key)
    # neither full nor overdue (idle is ignored: not work-conserving)
    assert batcher.plan(q, registry, now=1.0, idle=True) == []
    # deadline reached -> partial dispatch at the FIXED slot geometry
    [partial] = batcher.plan(q, registry, now=10.0)
    assert partial.key == key and partial.slots == 4
    assert len(partial.tickets) == 2

    # fill quota reached -> dispatch immediately, fresh remainder held
    clock.t = 10.4
    for seed in range(3, 8):
        q.submit(SampleRequest(seed=seed), key)
    [full] = batcher.plan(q, registry, now=10.5)
    assert len(full.tickets) == 4 and q.pending(key) == 1
    # flush drains the remainder regardless of fill/deadline
    [rest] = batcher.plan(q, registry, now=10.5, flush=True)
    assert len(rest.tickets) == 1 and rest.slots == 4
    assert len(q) == 0


def test_batcher_work_conserving_and_observed_stats():
    clock = FakeClock(0.0)
    q = RequestQueue(clock=clock)
    registry = EngineRegistry(make_factory())
    key = EngineKey("oracle", 8, "taa")
    batcher = Batcher(BatchingPolicy(max_batch=4, max_wait_s=10.0))
    q.submit(SampleRequest(seed=1), key)
    # work-conserving: an idle pipeline dispatches partials immediately...
    [d] = batcher.plan(q, registry, now=0.1, idle=True)
    assert len(d.tickets) == 1
    # ...but a busy pipeline holds them for fill/deadline
    q.submit(SampleRequest(seed=2), key)
    assert batcher.plan(q, registry, now=0.2, idle=False) == []
    assert batcher.observed(key) is None
    batcher.note(key, dict(slot_utilization=0.5, wall_s=1.0, pack_s=0.1))
    batcher.note(key, dict(slot_utilization=1.0, wall_s=3.0, pack_s=0.3))
    obs = batcher.observed(key)
    assert obs["dispatches"] == 2
    assert obs["slot_utilization"] == pytest.approx(0.75)
    assert obs["wall_s"] == pytest.approx(2.0)
    assert obs["pack_s"] == pytest.approx(0.2)


def test_batching_policy_validation():
    with pytest.raises(ValueError, match="max_batch"):
        BatchingPolicy(max_batch=0)
    with pytest.raises(ValueError, match="target_util"):
        BatchingPolicy(target_util=1.5)
    with pytest.raises(ValueError, match="max_wait_s"):
        BatchingPolicy(max_wait_s=-1.0)
    with pytest.raises(ValueError, match="depth"):
        ServingLoop(EngineRegistry(make_factory()), RequestQueue(), depth=0)
    with pytest.raises(ValueError, match="chunk_iters"):
        ServingLoop(EngineRegistry(make_factory()), RequestQueue(),
                    chunk_iters=-1)


def test_plan_refill_counts_inflight_refillable_slots():
    """Work-conserving admission counts the free lanes of an ACTIVE bank
    (the chunk runs with or without newcomers), while an idle bank applies
    the usual fill-or-deadline gate before lighting up the device."""
    clock = FakeClock(0.0)
    q = RequestQueue(clock=clock)
    key = EngineKey("oracle", 8, "taa")
    batcher = Batcher(BatchingPolicy(max_batch=4, max_wait_s=10.0))
    t1 = q.submit(SampleRequest(seed=1), key)
    # ACTIVE bank -> no fill/deadline gate: the lone ticket rides along now
    taken = batcher.plan_refill(q, key, 2, now=0.1, active=True)
    assert taken == [t1] and q.pending(key) == 0
    # idle bank: a partial refill waits for fill or deadline...
    t2 = q.submit(SampleRequest(seed=2), key)
    assert batcher.plan_refill(q, key, 4, now=0.2, active=False) == []
    # ...until the deadline passes
    assert batcher.plan_refill(q, key, 4, now=11.0, active=False) == [t2]
    # ...or the fill quota over the free lanes is met
    tks = [q.submit(SampleRequest(seed=s), key) for s in (3, 4)]
    assert batcher.plan_refill(q, key, 2, now=11.1, active=False) == tks
    # flush drains regardless; empty queue or no free lanes admit nothing
    t5 = q.submit(SampleRequest(seed=5), key)
    assert batcher.plan_refill(q, key, 0, now=11.2, active=True,
                               flush=True) == []
    assert batcher.plan_refill(q, key, 4, now=11.2, active=False,
                               flush=True) == [t5]
    assert batcher.plan_refill(q, key, 4, now=11.3, active=True) == []
    # non-work-conserving policies hold even for active banks
    strict = Batcher(BatchingPolicy(max_batch=4, max_wait_s=10.0,
                                    work_conserving=False))
    clock.t = 11.3
    q.submit(SampleRequest(seed=6), key)
    assert strict.plan_refill(q, key, 4, now=11.4, active=True) == []


# --- engine dispatch/collect halves ----------------------------------------

def test_dispatch_collect_halves_match_run_batch():
    T = 10
    engine = reference_engine(T)
    reqs = [SampleRequest(label=i % N_LABELS, seed=20 + i) for i in range(3)]
    pending = engine.dispatch(reqs, slots=4)
    assert isinstance(pending, PendingBatch)
    assert pending.slots == 4 and pending.pack_s >= 0.0
    assert not pending.diagnostics
    results = engine.collect(pending)
    ref = reference_engine(T).run_batch(reqs, batch_size=4)
    for got, want in zip(results, ref):
        assert np.array_equal(np.asarray(got.trajectory),
                              np.asarray(want.trajectory))
        assert (got.iters, got.nfe, got.converged) == \
            (want.iters, want.nfe, want.converged)
    # packing is timed separately from device wall time
    [report] = engine.last_dispatches
    assert report["pack_s"] >= 0.0 and report["wall_s"] > 0.0
    assert engine.stats["pack_s"] == pytest.approx(report["pack_s"])
    with pytest.raises(ValueError, match="at least one"):
        engine.dispatch([])
    with pytest.raises(ValueError, match="exceed"):
        engine.dispatch(reqs, slots=2)


# --- async serving == run_batch --------------------------------------------

def test_async_serving_bitwise_equals_run_batch():
    """Acceptance: async-served results are bitwise-equal to a blocking
    ``run_batch`` over the same requests (same slot geometry), warm and
    cold starts mixed in one dispatch."""
    T = 12
    key = EngineKey("oracle", T, "taa")
    [solved] = reference_engine(T).run_batch([SampleRequest(label=1, seed=3)])
    reqs = [SampleRequest(label=i % N_LABELS, seed=50 + i) for i in range(6)]
    reqs[2] = SampleRequest(label=1, seed=3,
                            init=WarmStart(solved.trajectory, t_init=6))

    registry = EngineRegistry(make_factory())
    queue = RequestQueue()
    loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=4)))
    tickets = [queue.submit(r, key) for r in reqs]
    loop.drain()
    assert loop.stats == {"dispatches": 2, "completed": 6, "failed": 0}

    ref = reference_engine(T).run_batch(reqs, batch_size=4)
    for ticket, want in zip(tickets, ref):
        got = ticket.result()
        assert np.array_equal(np.asarray(got.trajectory),
                              np.asarray(want.trajectory)), \
            f"async result diverged for {ticket.request}"
        assert (got.iters, got.nfe, got.converged) == \
            (want.iters, want.nfe, want.converged)
        assert ticket.latency_s is not None and ticket.latency_s >= 0.0
    # one fixed-slot geometry -> exactly one compilation
    assert registry.get(key).stats["traces"] == 1


def test_mixed_key_requests_route_to_their_engines_fifo_fair():
    """Requests interleaved across two EngineKeys land on the right engine
    (trajectory length proves the T), FIFO-fair per key."""
    k1 = EngineKey("oracle", 8, "taa")
    k2 = EngineKey("oracle", 14, "taa")
    counts = {}
    registry = EngineRegistry(make_factory(counts))

    # FIFO-fairness of the plan itself: interleaved submissions pop per key
    # in submission order, most-starved key first
    probe = RequestQueue()
    for i in range(8):
        probe.submit(SampleRequest(label=i % N_LABELS, seed=70 + i),
                     k1 if i % 2 == 0 else k2)
    plans = Batcher(BatchingPolicy(max_batch=4)).plan(
        probe, registry, flush=True)
    assert [p.key for p in plans] == [k1, k2]
    for plan in plans:
        seqnos = [t.seqno for t in plan.tickets]
        assert seqnos == sorted(seqnos) and len(seqnos) == 4

    # end-to-end: every request lands on its own key's engine
    queue = RequestQueue()
    loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=4)))
    tickets, keys = [], []
    for i in range(8):
        key = k1 if i % 2 == 0 else k2
        tickets.append(queue.submit(
            SampleRequest(label=i % N_LABELS, seed=70 + i), key))
        keys.append(key)
    loop.drain()
    for ticket, key in zip(tickets, keys):
        res = ticket.result()
        assert res.trajectory.shape[0] == key.T + 1
        assert res.request.label == ticket.request.label
        assert res.request.seed == ticket.request.seed
    assert counts == {k1: 1, k2: 1}            # one engine per key
    for key in (k1, k2):
        assert registry.get(key).stats["requests"] == 4
        assert registry.get(key).coeffs.T == key.T


# --- iteration-level (stepwise) serving --------------------------------------

def test_stepwise_loop_bitwise_equals_run_batch_with_mixed_budgets():
    """Acceptance: iteration-level serving — chunked solver state, lanes
    retiring/refilling mid-solve — reproduces the monolithic ``run_batch``
    bitwise over a mix of cold, warm-start (t_init), per-request-tau and
    quality-steps requests, with NO per-refill recompiles."""
    T = 12
    key = EngineKey("oracle", T, "taa")
    [solved] = reference_engine(T).run_batch([SampleRequest(label=1, seed=3)])
    reqs = [SampleRequest(label=i % N_LABELS, seed=50 + i) for i in range(6)]
    reqs[1] = SampleRequest(label=3, seed=51, tau=5e-2)
    reqs[2] = SampleRequest(label=1, seed=3,
                            init=WarmStart(solved.trajectory, t_init=6))
    reqs[4] = SampleRequest(label=0, seed=54, quality_steps=3)

    registry = EngineRegistry(make_factory())
    queue = RequestQueue()
    loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=4)),
                       chunk_iters=2)
    tickets = [queue.submit(r, key) for r in reqs]
    loop.drain()
    assert loop.inflight == 0 and loop.stats["completed"] == 6
    assert loop.stats["chunks"] > 0 and loop.stats["refills"] >= 2

    ref = reference_engine(T).run_batch(reqs, batch_size=4)
    for ticket, want in zip(tickets, ref):
        got = ticket.result()
        assert np.array_equal(np.asarray(got.trajectory),
                              np.asarray(want.trajectory)), \
            f"stepwise result diverged for {ticket.request}"
        assert (got.iters, got.nfe, got.converged, got.early_stopped) == \
            (want.iters, want.nfe, want.converged, want.early_stopped)
    # quality-steps lane early-exited
    assert tickets[4].result().early_stopped
    # open/init/merge/step/gather compiled exactly once each, refills
    # included
    engine = registry.get(key)
    assert engine.stats["stepwise_traces"] == 5
    polls_before = engine.stats["blocking_polls"]
    report = loop.bank_reports()[key]
    assert report["completed"] == 6 and report["occupied"] == 0
    assert 0.0 <= report["wasted_iter_frac"] < 1.0
    # reporting reuses the final round's cached poll (no extra fetch), and
    # the protocol counters ride on the report
    assert engine.stats["blocking_polls"] == polls_before
    assert report["gather_launches"] == report["harvests"] > 0
    assert report["blocking_polls"] > 0
    # retired-lane-only harvest: the whole drain fetched less than ONE
    # legacy full-bank harvest per retirement round would have
    T_plus_1_rows = (key.T + 1) * D * 4 + key.T * 4
    legacy = report["harvests"] * report["slots"] * T_plus_1_rows
    assert report["host_fetch_bytes"] < legacy


def test_stepwise_midsolve_refill_retires_late_arrivals_first():
    """Mid-solve refill semantics: with 2 lanes, a slow request and three
    quality-capped fast ones, the fast requests stream through the lane the
    first fast one vacates — all of them retiring BEFORE the slow request
    that started first (impossible for whole-batch dispatches, which hold
    every member to the slowest lane)."""
    T = 16
    key = EngineKey("oracle", T, "taa")
    slow = SampleRequest(label=1, seed=5, tau=1e-4)
    fast = [SampleRequest(label=i % N_LABELS, seed=30 + i, quality_steps=1)
            for i in range(3)]
    registry = EngineRegistry(make_factory())
    queue = RequestQueue()
    loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=2)),
                       chunk_iters=1)
    t_slow = queue.submit(slow, key)
    t_fast = [queue.submit(r, key) for r in fast]
    loop.drain()
    slow_res = t_slow.result()
    assert slow_res.converged and slow_res.iters > 3
    for t in t_fast:
        assert t.result().early_stopped and t.result().iters == 1
        assert t.completed_time < t_slow.completed_time, \
            "a 1-iteration request waited for the slow lane"
    # the single freed lane was refilled at least twice mid-solve
    assert loop.stats["refills"] >= 3
    # and the slow lane's solve was untouched by its neighbors churning
    [ref] = reference_engine(T).run_batch([slow])
    assert np.array_equal(np.asarray(slow_res.trajectory),
                          np.asarray(ref.trajectory))


def test_stepwise_loop_threaded_and_failure_paths():
    """Background-thread stepwise serving completes live arrivals; a
    request the engine rejects (per-request tau on seq) fails its own
    ticket and the loop keeps serving."""
    key = EngineKey("oracle", 8, "taa")
    registry = EngineRegistry(make_factory())
    registry.warmup(key, slots=4, chunk_iters=2)
    queue = RequestQueue()
    loop = ServingLoop(registry, queue,
                       Batcher(BatchingPolicy(max_batch=4, max_wait_s=0.01)),
                       chunk_iters=2)
    with loop:
        tickets = [queue.submit(
            SampleRequest(label=i % N_LABELS, seed=90 + i), key)
            for i in range(6)]
        results = [t.result(timeout=120) for t in tickets]
    assert all(r.converged for r in results)
    assert loop.stats["completed"] == 6 and loop.stats["failed"] == 0
    assert registry.get(key).stats["stepwise_traces"] == 5

    seq_key = EngineKey("oracle", 8, "seq")
    queue2 = RequestQueue()
    loop2 = ServingLoop(registry, queue2,
                        Batcher(BatchingPolicy(max_batch=2)), chunk_iters=2)
    bad = queue2.submit(SampleRequest(seed=1, tau=1e-2), seq_key)
    good = queue2.submit(SampleRequest(seed=2), seq_key)
    loop2.drain()
    with pytest.raises(ValueError, match="solver-iteration budgets"):
        bad.result()
    assert good.result().converged and good.result().iters == 8


def test_stepwise_seq_spec_chunks_and_matches_run_batch():
    """The sequential sampler serves through the same stepwise machinery
    (mode="seq" lanes, one timestep per iteration), bitwise-equal to its
    whole-batch dispatch."""
    key = EngineKey("oracle", 10, "seq")
    registry = EngineRegistry(make_factory())
    queue = RequestQueue()
    loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=2)),
                       chunk_iters=3)
    reqs = [SampleRequest(label=i % N_LABELS, seed=20 + i) for i in range(3)]
    tickets = [queue.submit(r, key) for r in reqs]
    loop.drain()
    ref = reference_engine(10, "seq").run_batch(reqs, batch_size=2)
    for t, r in zip(tickets, ref):
        got = t.result()
        assert np.array_equal(np.asarray(got.trajectory),
                              np.asarray(r.trajectory))
        assert got.iters == 10 and got.nfe == 10 and got.converged


# --- trajectory cache (warm-start groundwork) --------------------------------

def test_trajectory_cache_skeleton_on_registry():
    from repro.serving import TrajectoryCache
    registry = EngineRegistry(make_factory(), cache_capacity=2)
    key = EngineKey("oracle", 10, "taa")
    cache = registry.cache(key)
    assert registry.cache(key) is cache          # one cache per key
    assert isinstance(cache, TrajectoryCache) and len(cache) == 0
    assert cache.lookup(1) is None

    engine = registry.get(key)
    [r1] = engine.run_batch([SampleRequest(label=1, seed=3)])
    assert cache.record(r1) and len(cache) == 1
    ws = cache.lookup(1, t_init=5)
    assert ws is not None and ws.t_init == 5
    assert np.array_equal(np.asarray(ws.trajectory),
                          np.asarray(r1.trajectory))
    # warm-starting from the cache round-trips through the engine
    [warm] = engine.run_batch([SampleRequest(label=1, seed=3, init=ws)])
    assert warm.converged and warm.iters <= r1.iters

    # early-stopped results are refused: warm starts descend from solved
    # trajectories only
    [draft] = engine.run_batch([SampleRequest(label=2, seed=4,
                                              quality_steps=1)])
    assert draft.early_stopped and not cache.record(draft)
    # LRU capacity bound
    [r0] = engine.run_batch([SampleRequest(label=0, seed=5)])
    [r3] = engine.run_batch([SampleRequest(label=3, seed=6)])
    assert cache.record(r0) and cache.record(r3)
    assert len(cache) == 2 and cache.lookup(1) is None  # evicted
    with pytest.raises(ValueError, match="capacity"):
        TrajectoryCache(capacity=0)


def _solved(label, seed, n=8):
    """Minimal converged stand-in result for direct cache tests."""
    from types import SimpleNamespace
    value = label if isinstance(label, (int, float)) else 0.0
    return SimpleNamespace(
        request=SampleRequest(label=label, seed=seed),
        trajectory=np.full((n,), value, np.float32),     # 4*n bytes
        converged=True, early_stopped=False)


def test_trajectory_cache_byte_bound_and_counters():
    """Matured cache policy: LRU eviction under BOTH the entry-count and
    ``max_bytes`` bounds, hit/miss/evict counters, LRU refresh on hit,
    and refusal of entries that cannot fit the byte bound alone."""
    cache = TrajectoryCache(capacity=8, max_bytes=3 * 32)
    for label, seed in ((0, 1), (1, 2), (2, 3)):
        assert cache.record(_solved(label, seed))
    assert cache.stats() == dict(hits=0, misses=0, evictions=0,
                                 entries=3, bytes=3 * 32)
    # the byte bound (not capacity) evicts the LRU entry
    assert cache.record(_solved(3, 4))
    stats = cache.stats()
    assert stats["entries"] == 3 and stats["bytes"] == 3 * 32
    assert stats["evictions"] == 1
    assert cache.lookup(0) is None and cache.stats()["misses"] == 1
    # a hit LRU-refreshes: label 1 survives the next eviction, label 2 goes
    assert cache.lookup(1, seed=2) is not None
    assert cache.stats()["hits"] == 1
    assert cache.record(_solved(4, 5))
    assert cache.lookup(1) is not None and cache.lookup(2) is None
    # an entry that cannot fit alone is refused without evicting anything
    assert not cache.record(_solved(5, 6, n=100))
    assert cache.stats()["entries"] == 3
    with pytest.raises(ValueError, match="max_bytes"):
        TrajectoryCache(max_bytes=0)
    with pytest.raises(ValueError, match="neighborhood"):
        TrajectoryCache(neighborhood=-1)


def test_trajectory_cache_neighborhood_lookup():
    """Similarity beyond exact labels: exact ``(label, seed)`` is preferred,
    then the most-recent same-label entry, then the nearest label within
    the ``neighborhood`` distance threshold."""
    cache = TrajectoryCache(capacity=8, neighborhood=2)
    cache.record(_solved(0, 1))
    cache.record(_solved(5, 2))
    ws = cache.lookup(4)                    # |4-5| = 1 within threshold
    assert ws is not None and np.all(np.asarray(ws.trajectory) == 5)
    ws = cache.lookup(1)                    # |1-0| = 1 beats |1-5| = 4
    assert ws is not None and np.all(np.asarray(ws.trajectory) == 0)
    assert cache.lookup(8) is None          # |8-5| = 3 > neighborhood
    # exact (label, seed) wins over a nearer OTHER label
    cache.record(_solved(5, 9))
    exact = cache.lookup(5, seed=2)
    assert exact is not None
    # same-label fallback picks the most recent entry when the seed misses
    recent = cache.lookup(5, seed=404)
    assert recent is not None
    # non-numeric conditioning labels only ever match on equality
    cache.record(_solved("cat", 3))
    assert cache.lookup("cat") is not None
    assert cache.lookup("dog") is None


def test_submit_time_validation_and_cache_warm_start():
    """Tentpole: a malformed warm start fails ITS ticket at submit time —
    never reaching a packed dispatch — and the registry's cache
    auto-populates ``init`` for repeat submissions via the queue's
    ``warm_start`` hook (explicit inits win over the cache)."""
    T = 10
    key = EngineKey("oracle", T, "taa")
    registry = EngineRegistry(make_factory())
    queue = RequestQueue(validate=registry.validate_submit,
                         warm_start=registry.warm_start_for)

    bad_shape = queue.submit(SampleRequest(
        label=1, seed=2, init=WarmStart(np.zeros((3, D), np.float32))), key)
    assert bad_shape.done() and queue.pending(key) == 0    # not enqueued
    with pytest.raises(ValueError, match="trajectory shape"):
        bad_shape.result()
    bad_depth = queue.submit(SampleRequest(
        label=1, seed=2,
        init=WarmStart(np.zeros((T + 1, D), np.float32),
                       t_init=T + 3)), key)
    with pytest.raises(ValueError, match="t_init"):
        bad_depth.result()
    bad_dtype = queue.submit(SampleRequest(
        label=1, seed=2, init=WarmStart(np.zeros((T + 1, D), np.int32))),
        key)
    with pytest.raises(ValueError, match="floating"):
        bad_dtype.result()

    # populate the cache through a recording loop, then a repeat
    # submission warm-starts at submit time and a fresh label stays cold
    loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=2)),
                       chunk_iters=2, cache=True)
    cold = queue.submit(SampleRequest(label=1, seed=7), key)
    assert cold.request.init is None        # nothing cached yet
    loop.drain()
    cold_res = cold.result()
    assert cold_res.converged
    warm = queue.submit(SampleRequest(label=1, seed=7), key)
    assert warm.request.init is not None    # spliced in at submit
    assert np.array_equal(np.asarray(warm.request.init.trajectory),
                          np.asarray(cold_res.trajectory))
    other = queue.submit(SampleRequest(label=3, seed=8), key)
    assert other.request.init is None       # cache miss stays cold
    loop.drain()
    assert warm.result().converged
    assert warm.result().iters <= cold_res.iters
    assert other.result().converged
    stats = registry.cache(key).stats()
    assert stats["hits"] >= 1 and stats["misses"] >= 1
    # an explicit init wins over the cache hook
    explicit = WarmStart(cold_res.trajectory, t_init=0)
    keep = queue.submit(SampleRequest(label=1, seed=7, init=explicit), key)
    assert keep.request.init is explicit
    loop.drain()
    assert keep.result().converged


# --- two-tier draft-and-refine ----------------------------------------------

def test_two_tier_ticket_drafts_then_refines():
    """Tentpole: a quality-budgeted request resolves its DRAFT stage at the
    early exit (``on_draft`` + ``draft_result``), the planner re-enqueues
    a warm-started preemptible continuation on the SAME ticket, and the
    final result reaches full tolerance — with zero extra stepwise
    traces for the refine splices."""
    T = 12
    key = EngineKey("oracle", T, "taa")
    registry = EngineRegistry(make_factory())
    queue = RequestQueue()
    loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=4)),
                       chunk_iters=2, refiner=RefinePlanner(RefinePolicy()))
    drafts_seen = []
    tickets = []
    for i in range(6):
        req = SampleRequest(label=i % N_LABELS, seed=60 + i,
                            **({} if i % 3 == 0
                               else dict(quality_steps=1)))
        ticket = queue.submit(req, key)
        ticket.on_draft = drafts_seen.append
        tickets.append(ticket)
    loop.drain()
    assert registry.get(key).stats["stepwise_traces"] == 5
    for i, ticket in enumerate(tickets):
        final = ticket.result(timeout=0)
        draft = ticket.draft_result(timeout=0)
        assert ticket.done() and ticket.draft_done()
        assert final.converged and not final.early_stopped
        if i % 3 == 0:
            # single-stage: the final result IS the draft stage
            assert ticket.refines == 0 and draft is final
        else:
            assert ticket.refines == 1
            assert draft.early_stopped and draft.iters == 1
            assert ticket.draft_time <= ticket.completed_time
            # the continuation rode the same ticket at background tier
            assert ticket.request.preemptible
            assert ticket.request.priority == -1
            assert ticket.request.init is not None
            assert ticket.request.quality_steps is None
    assert len(drafts_seen) == 6           # fires for single-stage too
    assert loop.stats["drafts"] == 4 and loop.stats["refines"] == 4
    assert loop.stats["completed"] == 6
    # the refined final lands on the same fixed point as a cold solve
    [ref] = reference_engine(T).run_batch([SampleRequest(label=1, seed=61)])
    got = tickets[1].result()
    assert np.allclose(np.asarray(got.x0), np.asarray(ref.x0), atol=1e-2)


def test_urgent_arrivals_preempt_refine_lanes():
    """Satellite: refine lanes are background occupancy — when fresh
    non-preemptible arrivals outnumber the free lanes, the loop vacates
    preemptible refine lanes (tickets re-enqueued, warm start intact) so
    refinement never starves admission, and the preempted tickets still
    complete both stages."""
    T = 16
    key = EngineKey("oracle", T, "taa")
    registry = EngineRegistry(make_factory())
    queue = RequestQueue()
    loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=2)),
                       chunk_iters=1, refiner=RefinePlanner(RefinePolicy()))
    draft_tix = [queue.submit(SampleRequest(label=i, seed=10 + i,
                                            quality_steps=1), key)
                 for i in range(2)]
    # pump until both drafts resolved and their continuations occupy the
    # bank's (only) two lanes
    for _ in range(50):
        loop.pump(flush=True)
        if all(t.draft_done() for t in draft_tix) \
                and queue.pending(key) == 0 and loop.inflight == 2:
            break
    else:
        pytest.fail("refine continuations never occupied the lanes")
    assert all(t.request.preemptible for t in draft_tix)
    assert loop.stats["preemptions"] == 0
    urgent = [queue.submit(SampleRequest(label=2 + i, seed=20 + i), key)
              for i in range(2)]
    loop.pump(flush=True)
    assert loop.stats["preemptions"] >= 1   # refine lanes vacated
    loop.drain()
    for ticket in draft_tix + urgent:
        res = ticket.result(timeout=0)
        assert res.converged and not res.early_stopped
        assert ticket.done() and ticket.draft_done()
    assert all(t.refines == 1 for t in draft_tix)
    # preempted continuations kept their warm start (no cold restart)
    assert all(t.request.init is not None for t in draft_tix)
    assert registry.get(key).stats["stepwise_traces"] == 5


def test_serving_loop_threaded_live_arrivals():
    key = EngineKey("oracle", 8, "taa")
    registry = EngineRegistry(make_factory())
    registry.warmup(key, slots=4)
    queue = RequestQueue()
    loop = ServingLoop(registry, queue,
                       Batcher(BatchingPolicy(max_batch=4, max_wait_s=0.01)))
    with loop:
        tickets = []
        for i in range(6):
            tickets.append(queue.submit(
                SampleRequest(label=i % N_LABELS, seed=90 + i), key))
            time.sleep(0.002)
        results = [t.result(timeout=120) for t in tickets]
    assert all(r.converged for r in results)
    assert loop.stats["completed"] == 6 and loop.stats["failed"] == 0
    assert len(queue) == 0 and loop.inflight == 0


class _StubDevice:
    """Stands in for a device computation: is_ready()/wait() on an event."""

    def __init__(self):
        self._event = threading.Event()

    def is_ready(self):
        return self._event.is_set()

    def finish(self):
        self._event.set()

    def wait(self):
        self._event.wait()


class _StubEngine:
    """Engine double: dispatch hands out a pending whose 'device' the test
    controls, collect blocks on it — so out-of-order readiness is exact."""

    def __init__(self):
        from repro.sampling import Placement
        self.placement = Placement.host()
        self.last_dispatches = []
        self.pendings = []

    def dispatch(self, requests, slots=None):
        pending = PendingBatch(trajs=_StubDevice(), info={},
                               requests=list(requests), slots=slots or 1,
                               diagnostics=False, pack_s=0.0, t_dispatch=0.0)
        self.pendings.append(pending)
        return pending

    def collect(self, pending):
        pending.trajs.wait()
        return [f"served-{r.seed}" for r in pending.requests]


def test_serving_loop_collects_ready_batches_out_of_order():
    """A short batch that finishes behind a long in-flight one must resolve
    its tickets without waiting for the long batch (no head-of-line block),
    and new arrivals must keep dispatching into the free pipeline depth."""
    engines = {}

    class StubRegistry:
        def get(self, key):
            return engines.setdefault(key, _StubEngine())

    slow_key = EngineKey("stub", 10, "taa")
    fast_key = EngineKey("stub", 4, "taa")
    queue = RequestQueue()
    loop = ServingLoop(StubRegistry(), queue,
                       Batcher(BatchingPolicy(max_batch=2, max_wait_s=0.001)))
    with loop:
        slow = [queue.submit(SampleRequest(seed=s), slow_key) for s in (1, 2)]
        deadline = time.monotonic() + 30
        while not engines.get(slow_key, _StubEngine()).pendings \
                and time.monotonic() < deadline:
            time.sleep(0.001)              # slow batch now in flight
        fast = queue.submit(SampleRequest(seed=3), fast_key)
        deadline = time.monotonic() + 30
        while not engines.get(fast_key, _StubEngine()).pendings \
                and time.monotonic() < deadline:
            time.sleep(0.001)              # fast batch dispatched alongside
        engines[fast_key].pendings[0].trajs.finish()
        assert fast.result(timeout=30) == "served-3"
        assert not slow[0].done()          # long batch still computing
        engines[slow_key].pendings[0].trajs.finish()
        assert [t.result(timeout=30) for t in slow] == \
            ["served-1", "served-2"]
    assert loop.stats["completed"] == 3


def test_serving_loop_fails_tickets_not_the_loop():
    """A request an engine rejects (warm start on the sequential sampler)
    fails its own tickets; later dispatches still serve."""
    key = EngineKey("oracle", 8, "seq")
    registry = EngineRegistry(make_factory())
    queue = RequestQueue()
    loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=2)))
    [solved] = reference_engine(8).run_batch([SampleRequest(seed=1)])
    bad = queue.submit(
        SampleRequest(seed=2, init=WarmStart(solved.trajectory, 4)), key)
    loop.drain()
    good = queue.submit(SampleRequest(seed=3), key)
    loop.drain()
    with pytest.raises(ValueError, match="warm start"):
        bad.result()
    assert good.result().converged
    assert loop.stats["failed"] == 1 and loop.stats["completed"] == 1


def test_poisoned_key_fails_its_tickets_and_serving_continues():
    """A key whose engine factory raises (bad solver name) fails only its
    own tickets; other keys keep serving through the same loop."""
    good_key = EngineKey("oracle", 8, "taa")
    bad_key = EngineKey("oracle", 8, "nope")
    registry = EngineRegistry(make_factory())   # get_sampler("nope") raises
    queue = RequestQueue()
    loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=2)))
    bad = queue.submit(SampleRequest(seed=1), bad_key)
    good = queue.submit(SampleRequest(seed=2), good_key)
    loop.drain()
    with pytest.raises(KeyError, match="nope"):
        bad.result()
    assert good.result().converged
    assert len(queue) == 0


def test_pump_and_drain_refuse_while_background_thread_owns_the_loop():
    registry = EngineRegistry(make_factory())
    registry.warmup(EngineKey("oracle", 8, "taa"), slots=2)
    loop = ServingLoop(registry, RequestQueue(),
                       Batcher(BatchingPolicy(max_batch=2)))
    with loop:
        with pytest.raises(RuntimeError, match="background thread"):
            loop.pump()
        with pytest.raises(RuntimeError, match="background thread"):
            loop.drain()
    loop.drain()                               # fine again once stopped


# --- machine-readable bench results -----------------------------------------

def test_write_bench_json_merges_sections_and_stamps_schema(tmp_path):
    from benchmarks.common import BENCH_SCHEMA_VERSION, write_bench_json
    path = tmp_path / "BENCH_serving.json"
    write_bench_json("throughput", {"reqps": 2.0}, path=path)
    write_bench_json("async", {"speedup": 1.5}, path=path)
    data = json.loads(path.read_text())
    assert data == {"throughput": {"reqps": 2.0}, "async": {"speedup": 1.5},
                    "schema_version": BENCH_SCHEMA_VERSION}
    path.write_text("not json")
    write_bench_json("async", {"speedup": 2.0}, path=path)
    assert json.loads(path.read_text()) == {
        "async": {"speedup": 2.0},
        "schema_version": BENCH_SCHEMA_VERSION}


# --- sharded variant: async == run_batch under an 8-device mesh --------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import ddim_coeffs
from repro.diffusion.schedules import make_schedule
from repro.launch.mesh import make_mesh
from repro.sampling import (Placement, SampleRequest, SamplingEngine,
                            get_sampler)
from repro.serving import (Batcher, BatchingPolicy, EngineKey,
                           EngineRegistry, RequestQueue, ServingLoop)

D, N_LABELS = 16, 4
abar = jnp.asarray(make_schedule("linear", 1000)[0], jnp.float32)
key = jax.random.PRNGKey(0)
xstars = jax.random.normal(key, (N_LABELS, D))
W = jax.random.normal(jax.random.fold_in(key, 3), (D, D)) / np.sqrt(D)

def eps_apply(params, x, taus, y):
    ab = abar[jnp.clip(taus.astype(jnp.int32), 0, 999)][:, None]
    xs = xstars[jnp.clip(y, 0, N_LABELS - 1)]
    lin = (x - jnp.sqrt(ab) * xs) / jnp.sqrt(1.0 - ab + 1e-8)
    return lin + 0.3 * jnp.tanh(x @ W)

plc = Placement(mesh=make_mesh("debug", data_parallel=4, model_parallel=2))

def factory(k):
    return SamplingEngine(eps_apply, None, ddim_coeffs(k.T),
                          get_sampler(k.solver), sample_shape=(D,),
                          placement=plc)

k1 = EngineKey("oracle", 10, "taa")
k2 = EngineKey("oracle", 16, "taa")
reqs = [SampleRequest(label=i % N_LABELS, seed=50 + i) for i in range(10)]
keys = [k1 if i % 2 == 0 else k2 for i in range(10)]

registry = EngineRegistry(factory)
queue = RequestQueue()
# max_batch=3 rounds up to the mesh's 4 data shards: fixed 4-slot dispatches
loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=3)))
tickets = [queue.submit(r, k) for r, k in zip(reqs, keys)]
loop.drain()

out = {"slots": sorted({d["slots"] for e in registry.engines().values()
                        for d in e.last_dispatches}),
       "devices": sorted({d["devices"] for e in registry.engines().values()
                          for d in e.last_dispatches}),
       "traces": sorted(e.stats["traces"]
                        for e in registry.engines().values()),
       "pack_reported": all("pack_s" in d
                            for e in registry.engines().values()
                            for d in e.last_dispatches)}

counts_equal, max_diff, max_abs = True, 0.0, 0.0
for kk in (k1, k2):
    mine = [(t, i) for i, (t, k) in enumerate(zip(tickets, keys)) if k == kk]
    host = SamplingEngine(eps_apply, None, ddim_coeffs(kk.T),
                          get_sampler(kk.solver), sample_shape=(D,))
    ref = host.run_batch([reqs[i] for _, i in mine], batch_size=4)
    for (t, _), r in zip(mine, ref):
        got = t.result()
        diff = np.asarray(got.trajectory) - np.asarray(r.trajectory)
        max_diff = max(max_diff, float(np.max(np.abs(diff))))
        max_abs = max(max_abs, float(np.max(np.abs(r.trajectory))))
        counts_equal = counts_equal and got.iters == r.iters \
            and got.nfe == r.nfe
out["counts_equal"] = bool(counts_equal)
out["max_diff"] = max_diff
out["max_abs"] = max_abs
out["loop"] = loop.stats
print("RESULT " + json.dumps(out))
"""


@pytest.mark.mesh
def test_async_serving_sharded_matches_host_run_batch():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).resolve().parent.parent, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[7:])
    # The sharded program batches 1 request per data shard, the host one 4:
    # XLA:CPU (jax 0.9) compiles the two batch geometries differently, so
    # f32 rounding differs and compounds over the solve (observed max
    # |diff| 3.1e-5 on trajectories of max magnitude 3.4, ~1e-5 relative).
    # 1e-4 leaves 3x headroom and is 10x under the solver's stopping
    # tolerance tau=1e-3.  Iteration and NFE counts stay exact.
    assert out["max_diff"] <= 1e-4, \
        f"async sharded serving diverged from host run_batch: {out}"
    assert out["counts_equal"], out
    assert out["slots"] == [4]                 # 3 rounded up to 4 data shards
    assert out["devices"] == [8]
    assert out["traces"] == [1, 1]             # one compile per key
    assert out["pack_reported"]
    assert out["loop"] == {"dispatches": 4, "completed": 10, "failed": 0}


STEPWISE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import ddim_coeffs
from repro.diffusion.schedules import make_schedule
from repro.launch.mesh import make_mesh
from repro.sampling import (Placement, SampleRequest, SamplingEngine,
                            WarmStart, get_sampler)
from repro.serving import (Batcher, BatchingPolicy, EngineKey,
                           EngineRegistry, RequestQueue, ServingLoop)

D, N_LABELS = 16, 4
abar = jnp.asarray(make_schedule("linear", 1000)[0], jnp.float32)
key = jax.random.PRNGKey(0)
xstars = jax.random.normal(key, (N_LABELS, D))
W = jax.random.normal(jax.random.fold_in(key, 3), (D, D)) / np.sqrt(D)

def eps_apply(params, x, taus, y):
    ab = abar[jnp.clip(taus.astype(jnp.int32), 0, 999)][:, None]
    xs = xstars[jnp.clip(y, 0, N_LABELS - 1)]
    lin = (x - jnp.sqrt(ab) * xs) / jnp.sqrt(1.0 - ab + 1e-8)
    return lin + 0.3 * jnp.tanh(x @ W)

plc = Placement(mesh=make_mesh("debug", data_parallel=4, model_parallel=2))

def factory(k):
    return SamplingEngine(eps_apply, None, ddim_coeffs(k.T),
                          get_sampler(k.solver), sample_shape=(D,),
                          placement=plc)

T = 12
k1 = EngineKey("oracle", T, "taa")
host = SamplingEngine(eps_apply, None, ddim_coeffs(T), get_sampler("taa"),
                      sample_shape=(D,))
[solved] = host.run_batch([SampleRequest(label=1, seed=3)])
reqs = [SampleRequest(label=i % N_LABELS, seed=50 + i) for i in range(10)]
reqs[1] = SampleRequest(label=3, seed=51, tau=5e-2)
reqs[2] = SampleRequest(label=1, seed=3,
                        init=WarmStart(solved.trajectory, t_init=6))
reqs[5] = SampleRequest(label=0, seed=55, quality_steps=3)

registry = EngineRegistry(factory)
queue = RequestQueue()
# max_batch=3 rounds up to the mesh's 4 data shards: fixed 4-lane bank
loop = ServingLoop(registry, queue, Batcher(BatchingPolicy(max_batch=3)),
                   chunk_iters=2)
tickets = [queue.submit(r, k1) for r in reqs]
loop.drain()

ref = host.run_batch(reqs, batch_size=4)
equal = True
for t, r in zip(tickets, ref):
    got = t.result()
    equal = equal and np.array_equal(np.asarray(got.trajectory),
                                     np.asarray(r.trajectory)) \
        and got.iters == r.iters and got.nfe == r.nfe \
        and got.early_stopped == r.early_stopped
engine = registry.get(k1)
report = loop.bank_reports()[k1]
out = {"equal": bool(equal),
       "slots": report["slots"], "devices": report["devices"],
       "stepwise_traces": engine.stats["stepwise_traces"],
       "refills": report["refills"], "completed": report["completed"],
       "loop_completed": loop.stats["completed"],
       "failed": loop.stats["failed"]}
print("RESULT " + json.dumps(out))
"""


@pytest.mark.mesh
def test_stepwise_serving_sharded_matches_host_run_batch():
    """Acceptance: the chunked stepwise loop on the 8-device debug mesh —
    lanes sharded 4-way over data, denoiser TP over model, mid-solve
    refills included — reproduces the HOST engine's monolithic run_batch
    bitwise, with the stepwise programs compiled exactly once each."""
    proc = subprocess.run(
        [sys.executable, "-c", STEPWISE_SCRIPT], capture_output=True,
        text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).resolve().parent.parent, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[7:])
    assert out["equal"], \
        "sharded stepwise serving diverged from host run_batch"
    assert out["slots"] == 4 and out["devices"] == 8
    assert out["stepwise_traces"] == 5   # open/init/merge/step/gather, once
    assert out["refills"] >= 3                 # lanes recycled mid-solve
    assert out["completed"] == 10 and out["loop_completed"] == 10
    assert out["failed"] == 0
