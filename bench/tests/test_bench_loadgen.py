"""The load generator: a seed fixes the schedule, the rate is the one
asked for, the open loop stamps due times and reports its lateness, and
the DiT's draws are those the benchmark has always made."""
import collections
import hashlib
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import harness  # noqa: E402
import loadgen  # noqa: E402

DIT = harness.load_model("dit-xl")


def classes(n):
    """The DiT's condition: a class label out of ``n``."""
    return lambda rng: DIT.draw_condition(rng, {"num_classes": n})


def _key(schedule):
    return [(s.cond, s.noise_seed, s.due) for s in schedule]


def _digest(rows) -> str:
    return hashlib.sha256(json.dumps(rows).encode()).hexdigest()[:16]


def test_seed_fixes_the_schedule():
    """The arrival seed fixes the due times, the run's seed the requests:
    every run's seed meets the same arrivals with other requests."""
    a = loadgen.poisson_schedule(1, 2**31 + 5, 1.15, 51, classes(1000))
    b = loadgen.poisson_schedule(1, 2**31 + 5, 1.15, 51, classes(1000))
    c = loadgen.poisson_schedule(1, 2**31 + 6, 1.15, 51, classes(1000))
    d = loadgen.poisson_schedule(2, 2**31 + 5, 1.15, 51, classes(1000))
    assert _key(a) == _key(b)
    assert [s.due for s in a] == [s.due for s in c]
    assert [(s.cond, s.noise_seed) for s in a] != \
        [(s.cond, s.noise_seed) for s in c]
    assert [s.due for s in a] != [s.due for s in d]
    assert [(s.cond, s.noise_seed) for s in a] == \
        [(s.cond, s.noise_seed) for s in d]


def test_poisson_draws_are_pinned():
    """The first 20 (label, noise seed, due) of the DiT's Poisson schedule
    at 10 classes, as the benchmark drew them before its model-specific
    code moved into ``models/dit-xl.py``."""
    rows = [list(k) for k in _key(loadgen.poisson_schedule(
        1, 2**31 + 5, 1.15, 51, classes(10))[:20])]
    assert rows[:3] == [[2, 512621313, 0.27294013782742266],
                        [6, 104445283, 0.6758111209392647],
                        [0, 1063514113, 0.9620585425228471]]
    assert _digest(rows) == "2bef547ea5314ebc"


def test_closed_loop_draws_are_pinned():
    """Likewise the first 20 (label, noise seed) of closed-loop clients 0
    and 3 of 4 (a client's due time is the clock's)."""
    got = collections.defaultdict(list)
    lock = threading.Lock()

    def submit(cond, noise, due):
        with lock:
            got[threading.current_thread().name].append([cond, noise])
        ev = threading.Event()
        ev.set()
        return _Ticket(ev)

    gen = loadgen.ClosedLoop(2**31 + 5, clients=4, stagger_s=0.0,
                             draw_condition=classes(10), submit=submit,
                             result_timeout=5)
    gen.start(time.monotonic())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        with lock:
            if all(len(got[f"client-{c}"]) >= 20 for c in range(4)):
                break
        time.sleep(0.001)
    gen.stop(timeout=5)
    assert all(not th.is_alive() for th in gen._threads)
    first = {c: got[f"client-{c}"][:20] for c in (0, 3)}
    assert first[0][:3] == [[1, 493334475], [4, 779250127], [8, 543098909]]
    assert first[3][:3] == [[3, 792995924], [9, 465080831], [1, 597099640]]
    assert _digest(first[0]) == "b005a796c55895c1"
    assert _digest(first[3]) == "114b49f53e47b1b3"


@pytest.mark.parametrize("rate,seconds", [(0.85, 51), (3.0, 20), (40, 5)])
def test_every_seed_offers_the_rate_asked(rate, seconds):
    for seed in range(20):
        sched = loadgen.poisson_schedule(seed, seed, rate, seconds,
                                         classes(10))
        due = [s.due for s in sched]
        assert len(sched) == round(rate * seconds)
        assert due == sorted(due) and 0 <= due[0] and due[-1] < seconds
        assert all(0 <= s.cond < 10 for s in sched)


def test_gaps_are_exponential():
    """Uniform due times given their count: the gaps' mean is 1/rate and
    their coefficient of variation is near 1, as a Poisson process's."""
    gaps = np.concatenate([
        np.diff([s.due for s in loadgen.poisson_schedule(seed, 0, 2.0, 500,
                                                        classes(10))])
        for seed in range(4)])
    assert gaps.mean() == pytest.approx(0.5, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.08)


class _Ticket:
    def __init__(self, done):
        self.done_event = done

    def result(self, timeout=None):
        if not self.done_event.wait(timeout):
            raise TimeoutError
        return "ok"


def test_open_loop_submits_at_due_times():
    sched = loadgen.poisson_schedule(3, 3, 50.0, 0.4, classes(10))
    got = []

    def submit(cond, noise, due):
        got.append((cond, noise, due, time.monotonic()))
        ev = threading.Event()
        ev.set()
        return _Ticket(ev)

    gen = loadgen.OpenLoop(sched, submit)
    t0 = time.monotonic() + 0.05
    gen.start(t0)
    gen.stop(timeout=5)
    assert [g[:3] for g in got] == [(s.cond, s.noise_seed, s.due)
                                    for s in sched]
    assert all(s.due >= t0 for s in sched)
    late = gen.lateness()
    assert len(late) == len(sched) and min(late) >= 0
    assert all(g[3] >= g[2] for g in got)


def test_closed_loop_clients_wait_for_their_last_request():
    """Each client has one request open at a time and sends the next the
    moment it returns."""
    events = []
    lock = threading.Lock()

    def submit(cond, noise, due):
        ev = threading.Event()
        with lock:
            events.append(ev)
        return _Ticket(ev)

    def open_count():
        with lock:
            return sum(not ev.is_set() for ev in events)

    gen = loadgen.ClosedLoop(7, clients=3, stagger_s=0.0,
                             draw_condition=classes(10),
                             submit=submit, result_timeout=5)
    gen.start(time.monotonic())
    for rounds in (1, 2, 3):
        deadline = time.monotonic() + 5
        while len(events) < 3 * rounds and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(events) == 3 * rounds and open_count() == 3
        if rounds < 3:
            with lock:
                for ev in events:
                    ev.set()
    gen._stop.set()
    with lock:
        for ev in events:
            ev.set()
    gen.stop(timeout=5)
    assert all(not th.is_alive() for th in gen._threads)
    assert len(gen.sent) == 9 and not gen.errors
