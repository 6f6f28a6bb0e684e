"""Closed- and open-loop request generators, timed on the host clock.

Both drive one ``submit(payload) -> Future | None`` callable (None: the
server refused the request) and record, per request, when it was due,
when it was sent, when its result arrived and what it was. They run the
whole timeline of a run: a warm phase that counts as set-up, the measured
window, and a drain of what is still outstanding when the window closes,
waited for up to ``grace_s`` seconds.

- Closed loop: ``outstanding`` clients, each sending its next request the
  moment its previous one returns. A request is due when it is sent.
- Open loop: requests are due on a fixed schedule, sent on time whether
  or not earlier ones have returned. Latency is timed from the due time,
  so a stall of the generator or the server is charged to every request
  it delays. How late the generator sent is recorded.
"""

from __future__ import annotations

import math
import queue
import threading
import time
from typing import Callable, List, Optional

import numpy as np

INF = float("inf")
now = time.perf_counter


class Log:
    """Per-request record of one run (index = order of sending)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.payload: List[int] = []      # payload index (which frames)
        self.due: List[float] = []
        self.sent: List[float] = []
        self.done: List[float] = []       # nan until the result arrives
        self.out: List[Optional[np.ndarray]] = []
        self.error: List[Optional[BaseException]] = []
        self.refused: List[bool] = []
        self.t0 = math.nan                # window start
        self.t1 = math.nan                # window end

    def add(self, payload: int, due: float, sent: float) -> int:
        with self._lock:
            self.payload.append(payload)
            self.due.append(due)
            self.sent.append(sent)
            self.done.append(math.nan)
            self.out.append(None)
            self.error.append(None)
            self.refused.append(False)
            return len(self.payload) - 1

    def finish(self, i: int, fut) -> None:
        t = now()
        exc = fut.exception()
        with self._lock:
            self.done[i] = t
            if exc is None:
                self.out[i] = fut.result()
            else:
                self.error[i] = exc

    def in_window(self) -> np.ndarray:
        """Indices of the requests due inside the window."""
        due = np.asarray(self.due)
        return np.nonzero((due >= self.t0) & (due < self.t1))[0]

    def latencies_ms(self, idx: np.ndarray) -> np.ndarray:
        """Due -> result, in ms; +inf for a refused, failed or unanswered
        request."""
        due = np.asarray(self.due)[idx]
        done = np.asarray(self.done)[idx]
        lat = (done - due) * 1e3
        bad = np.isnan(done) | np.asarray(
            [self.error[i] is not None or self.refused[i] for i in idx],
            dtype=bool)
        lat[bad] = INF
        return lat

    def completed_in_window(self, frames_per_request: int) -> int:
        """Frames whose results arrived inside the window."""
        done = np.asarray(self.done)
        ok = np.asarray([e is None for e in self.error], dtype=bool)
        hit = (done >= self.t0) & (done < self.t1) & ok
        return int(hit.sum()) * frames_per_request

    def completed_by_second(self, frames_per_request: int) -> List[int]:
        """Frames whose results arrived in each whole second of the
        window."""
        done = np.asarray(self.done)
        ok = np.asarray([e is None for e in self.error], dtype=bool)
        t = done[ok & (done >= self.t0) & (done < self.t1)] - self.t0
        n = int(math.ceil(self.t1 - self.t0))
        counts = np.bincount(t.astype(int), minlength=n)[:n]
        return [int(c) * frames_per_request for c in counts]


def _wait_all(log: Log, pending: set, lock: threading.Lock,
              grace_s: float) -> None:
    deadline = now() + grace_s
    while now() < deadline:
        with lock:
            if not pending:
                return
        time.sleep(1e-3)


def closed_loop(submit: Callable, n_payloads: int, order: np.ndarray,
                outstanding: int, warm_requests: int, seconds: float,
                grace_s: float = 60.0,
                on_window: Optional[Callable] = None) -> Log:
    """``outstanding`` clients in a closed loop. The window opens once
    ``warm_requests`` results have come back, and lasts ``seconds``;
    clients send nothing after it closes. ``on_window(t0, t1)`` is called
    as it opens."""
    log = Log()
    ready: queue.Queue = queue.Queue()
    pending: set = set()
    lock = threading.Lock()
    nxt = [0]

    def on_done(i, fut):
        log.finish(i, fut)
        with lock:
            pending.discard(i)
        ready.put(i)

    def send() -> None:
        k = nxt[0]
        nxt[0] += 1
        p = int(order[k % len(order)]) % n_payloads
        t = now()
        fut = submit(p)
        i = log.add(p, t, t)
        if fut is None:
            log.refused[i] = True
            ready.put(i)
            return
        with lock:
            pending.add(i)
        fut.add_done_callback(lambda f, i=i: on_done(i, f))

    for _ in range(outstanding):
        send()
    returned = 0
    while True:
        ready.get()
        returned += 1
        t = now()
        if returned == warm_requests:
            log.t0, log.t1 = t, t + seconds
            if on_window is not None:
                on_window(log.t0, log.t1)
        if not math.isnan(log.t1) and t >= log.t1:
            break
        send()
    _wait_all(log, pending, lock, grace_s)
    return log


def poisson_gaps(rate: float, seconds: float, schedule_seed: int,
                 seed: int) -> np.ndarray:
    """Exponential gaps of one fixed schedule, in an order drawn from
    ``seed``.

    The gaps come from ``schedule_seed`` and are scaled to fill
    ``seconds`` exactly, so every run offers the same number of requests
    with the same set of gaps; the run's seed only shuffles them."""
    n = max(int(round(rate * seconds)), 1)
    gaps = np.random.default_rng(schedule_seed).exponential(1.0 / rate, n)
    gaps *= seconds / gaps.sum()
    return np.random.default_rng([seed, 2]).permutation(gaps)


def open_loop(submit: Callable, n_payloads: int, order: np.ndarray,
              warm_gaps: np.ndarray, gaps: np.ndarray,
              grace_s: float = 60.0,
              on_window: Optional[Callable] = None) -> Log:
    """Send on a schedule: ``warm_gaps`` (set-up), then ``gaps`` (the
    window, which opens at the first window request's due time).
    ``on_window(t0, t1)`` is called before the first request is sent."""
    log = Log()
    pending: set = set()
    lock = threading.Lock()

    def on_done(i, fut):
        log.finish(i, fut)
        with lock:
            pending.discard(i)

    def dues(start, g):
        # the first request is due at ``start`` itself
        return start + np.concatenate([[0.0], np.cumsum(g)[:-1]])[:len(g)]

    t_base = now() + 1e-3
    warm_due = dues(t_base, warm_gaps)
    t0 = t_base + float(warm_gaps.sum())
    win_due = dues(t0, gaps)
    log.t0, log.t1 = t0, t0 + float(gaps.sum())
    if on_window is not None:
        on_window(log.t0, log.t1)
    for k, due in enumerate(np.concatenate([warm_due, win_due])):
        delay = due - now()
        if delay > 0:
            time.sleep(delay)
        p = int(order[k % len(order)]) % n_payloads
        t = now()
        fut = submit(p)
        i = log.add(p, float(due), t)
        if fut is None:
            log.refused[i] = True
            continue
        with lock:
            pending.add(i)
        fut.add_done_callback(lambda f, i=i: on_done(i, f))
    _wait_all(log, pending, lock, grace_s)
    return log


def lateness(log: Log, idx: np.ndarray) -> tuple:
    """(requests sent more than 1 ms after their due time, the largest
    lateness in ms) over ``idx``."""
    late = (np.asarray(log.sent)[idx] - np.asarray(log.due)[idx]) * 1e3
    if late.size == 0:
        return 0, 0.0
    return int((late > 1.0).sum()), float(late.max())
