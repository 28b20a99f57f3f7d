"""The load generator: a seed fixes the schedule, the rate is the one
asked for, and the open loop stamps due times and reports its lateness."""
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import loadgen  # noqa: E402


def _key(schedule):
    return [(s.label, s.noise_seed, s.due) for s in schedule]


def test_seed_fixes_the_schedule():
    """The arrival seed fixes the due times, the run's seed the requests:
    every run's seed meets the same arrivals with other requests."""
    a = loadgen.poisson_schedule(1, 2**31 + 5, 1.15, 51, 1000)
    b = loadgen.poisson_schedule(1, 2**31 + 5, 1.15, 51, 1000)
    c = loadgen.poisson_schedule(1, 2**31 + 6, 1.15, 51, 1000)
    d = loadgen.poisson_schedule(2, 2**31 + 5, 1.15, 51, 1000)
    assert _key(a) == _key(b)
    assert [s.due for s in a] == [s.due for s in c]
    assert [(s.label, s.noise_seed) for s in a] != \
        [(s.label, s.noise_seed) for s in c]
    assert [s.due for s in a] != [s.due for s in d]
    assert [(s.label, s.noise_seed) for s in a] == \
        [(s.label, s.noise_seed) for s in d]


@pytest.mark.parametrize("rate,seconds", [(0.85, 51), (3.0, 20), (40, 5)])
def test_every_seed_offers_the_rate_asked(rate, seconds):
    for seed in range(20):
        sched = loadgen.poisson_schedule(seed, seed, rate, seconds, 10)
        due = [s.due for s in sched]
        assert len(sched) == round(rate * seconds)
        assert due == sorted(due) and 0 <= due[0] and due[-1] < seconds
        assert all(0 <= s.label < 10 for s in sched)


def test_gaps_are_exponential():
    """Uniform due times given their count: the gaps' mean is 1/rate and
    their coefficient of variation is near 1, as a Poisson process's."""
    gaps = np.concatenate([
        np.diff([s.due for s in loadgen.poisson_schedule(seed, 0, 2.0, 500,
                                                        10)])
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
    sched = loadgen.poisson_schedule(3, 3, 50.0, 0.4, 10)
    got = []

    def submit(label, noise, due):
        got.append((label, noise, due, time.monotonic()))
        ev = threading.Event()
        ev.set()
        return _Ticket(ev)

    gen = loadgen.OpenLoop(sched, submit)
    t0 = time.monotonic() + 0.05
    gen.start(t0)
    gen.stop(timeout=5)
    assert [g[:3] for g in got] == [(s.label, s.noise_seed, s.due)
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

    def submit(label, noise, due):
        ev = threading.Event()
        with lock:
            events.append(ev)
        return _Ticket(ev)

    def open_count():
        with lock:
            return sum(not ev.is_set() for ev in events)

    gen = loadgen.ClosedLoop(7, clients=3, stagger_s=0.0, num_classes=10,
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
