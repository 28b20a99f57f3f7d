"""The one general load generator: every traffic mix is a data file of
parameters that this module reads.

Open loop (``"arrivals": "poisson"``): independent users.  The traffic
file's ``arrival_seed`` fixes the number of requests, round(rate *
seconds), and their due times, drawn uniform over the window and sorted,
which is a Poisson process conditioned on its count; the run's seed draws
the requests themselves.  Every seed thus offers the same arrivals, with
other conditions and noises, so the tail does not move with the clustering
a seed would draw.  Each
request is submitted at its due time on the queue's clock and carries that
due time as its ``arrival_time``, so a late generator or a stalled server
shows in the latency of every later request, and the generator's own
lateness (submit time minus due time) is reported beside it.

Closed loop (``"arrivals": "closed"``): ``clients`` callers, each sending
its next request the moment its last one returns, the first ones spaced
``client_stagger_s`` apart.  A client's requests are drawn from the seed
and the client's index.

Each request's condition is drawn from the seed by ``draw_condition(rng)``
(the model module's, ``models/<arch>.py``), then its noise seed.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, List, Optional

import numpy as np


@dataclasses.dataclass
class Sent:
    """One request as the generator sent it."""
    cond: Any                       # the model module's condition
    noise_seed: int
    due: float                      # queue clock
    submitted: Optional[float] = None
    ticket: object = None


def draw_request(rng: np.random.Generator, draw_condition: Callable):
    """(condition, noise seed) of the next request."""
    return draw_condition(rng), int(rng.integers(1 << 30))


def poisson_schedule(arrival_seed: int, seed: int, rate_per_s: float,
                     seconds: float, draw_condition: Callable) -> List[Sent]:
    """Due times (seconds after the window opens), drawn from
    ``arrival_seed``, and requests, drawn from ``seed``."""
    n = int(round(rate_per_s * seconds))
    due = np.sort(np.random.default_rng([int(arrival_seed), 1])
                  .uniform(0.0, seconds, n))
    rng = np.random.default_rng([int(seed), 1])
    return [Sent(*draw_request(rng, draw_condition), due=float(t))
            for t in due]


class OpenLoop:
    """Submits a schedule at its due times from one thread."""

    def __init__(self, schedule: List[Sent], submit: Callable,
                 clock: Callable[[], float] = time.monotonic):
        self.sent = schedule
        self._submit = submit
        self._clock = clock
        self._thread: Optional[threading.Thread] = None

    def start(self, t0: float) -> None:
        for s in self.sent:
            s.due += t0
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="open-loop")
        self._thread.start()

    def _run(self) -> None:
        for s in self.sent:
            wait = s.due - self._clock()
            if wait > 0:
                time.sleep(wait)
            s.submitted = self._clock()
            s.ticket = self._submit(s.cond, s.noise_seed, s.due)

    def stop(self, timeout: float) -> None:
        """Every request of the schedule is due inside the window, so the
        thread has ended, or ends at once, when the window closes."""
        self._thread.join(timeout)

    def lateness(self) -> List[float]:
        return [s.submitted - s.due for s in self.sent
                if s.submitted is not None]


class ClosedLoop:
    """``clients`` threads, each with its own request stream."""

    def __init__(self, seed: int, clients: int, stagger_s: float,
                 draw_condition: Callable, submit: Callable,
                 result_timeout: float,
                 clock: Callable[[], float] = time.monotonic):
        self.sent: List[Sent] = []
        self._lock = threading.Lock()
        self._seed = seed
        self._clients = clients
        self._stagger = stagger_s
        self._draw_condition = draw_condition
        self._submit = submit
        self._timeout = result_timeout
        self._clock = clock
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self.errors: List[BaseException] = []

    def start(self, t0: float) -> None:
        for c in range(self._clients):
            th = threading.Thread(target=self._run, args=(c, t0),
                                  daemon=True, name=f"client-{c}")
            self._threads.append(th)
            th.start()

    def _run(self, client: int, t0: float) -> None:
        rng = np.random.default_rng([int(self._seed), 2, client])
        start = t0 + client * self._stagger
        wait = start - self._clock()
        if wait > 0 and self._stop.wait(wait):
            return
        while not self._stop.is_set():
            cond, noise = draw_request(rng, self._draw_condition)
            now = self._clock()
            s = Sent(cond, noise, due=now, submitted=now)
            s.ticket = self._submit(cond, noise, now)
            with self._lock:
                self.sent.append(s)
            try:
                s.ticket.result(timeout=self._timeout)
            except Exception as error:  # noqa: BLE001 — counted as failed
                self.errors.append(error)
                return

    def stop(self, timeout: float) -> None:
        """Stop sending; each client returns once its open request does."""
        self._stop.set()
        deadline = self._clock() + timeout
        for th in self._threads:
            th.join(max(deadline - self._clock(), 0.0))

    def lateness(self) -> List[float]:
        return []
