"""The generators time from the due time and count refusals as +inf."""

import math
import threading
import time
import types
from concurrent.futures import Future

import numpy as np

import loadgen


class FakeServer:
    """Answers each request after ``service_s`` on a thread; refuses the
    payloads in ``refuse``; blocks the caller of request ``stall_at`` for
    ``stall_s`` inside submit."""

    def __init__(self, service_s=0.001, refuse=(), stall_at=None,
                 stall_s=0.0):
        self.service_s = service_s
        self.refuse = set(refuse)
        self.stall_at = stall_at
        self.stall_s = stall_s
        self.calls = 0
        self.threads = []

    def submit(self, p):
        k = self.calls
        self.calls += 1
        if k == self.stall_at:
            time.sleep(self.stall_s)
        if p in self.refuse:
            return None
        fut = Future()

        def answer():
            time.sleep(self.service_s)
            fut.set_result(np.full((1, 2), float(p), np.float32))

        t = threading.Thread(target=answer)
        t.start()
        self.threads.append(t)
        return fut

    def join(self):
        for t in self.threads:
            t.join(5.0)
            assert not t.is_alive()


def test_poisson_gaps_same_schedule_for_every_seed():
    a = loadgen.poisson_gaps(500.0, 2.0, 7, seed=1)
    b = loadgen.poisson_gaps(500.0, 2.0, 7, seed=2**40 + 3)
    assert len(a) == len(b) == 1000
    assert math.isclose(a.sum(), 2.0) and math.isclose(b.sum(), 2.0)
    assert np.array_equal(np.sort(a), np.sort(b))
    assert not np.array_equal(a, b)


def test_open_loop_charges_a_stall_to_the_requests_it_delays():
    # 40 requests 5 ms apart; submit blocks 100 ms on the 10th (the first
    # window request), so the ones due during the stall go out late
    srv = FakeServer(service_s=0.001, stall_at=10, stall_s=0.1)
    order = np.arange(50)
    log = loadgen.open_loop(srv.submit, 50, order,
                            warm_gaps=np.full(10, 0.005),
                            gaps=np.full(30, 0.005), grace_s=5.0)
    srv.join()
    win = log.in_window()
    assert len(win) == 30
    lat = log.latencies_ms(win)
    # the stalled request and the ~20 due while it blocked are each
    # charged the wait since their due time
    assert lat[0] >= 100.0
    assert (lat[1:15] >= 30.0).all()
    # the sent time is late by the same amount, and reported as such
    late_n, late_max = loadgen.lateness(log, win)
    assert late_n >= 15 and late_max >= 90.0
    # a latency from the send time would have hidden it
    sent = np.asarray(log.sent)[win]
    done = np.asarray(log.done)[win]
    assert ((done - sent) * 1e3)[5] < 30.0


def test_refused_and_unanswered_count_as_inf():
    srv = FakeServer(service_s=0.001, refuse={3, 4})
    order = np.arange(10)
    log = loadgen.open_loop(srv.submit, 10, order, warm_gaps=np.zeros(0),
                            gaps=np.full(10, 0.002), grace_s=5.0)
    srv.join()
    lat = log.latencies_ms(log.in_window())
    assert np.isinf(lat).sum() == 2
    assert np.isinf(lat[3]) and np.isinf(lat[4])
    assert np.isfinite(lat[[0, 1, 2, 5, 6, 7, 8, 9]]).all()
    # a request that never returned is +inf as well
    log.done[0] = math.nan
    assert np.isinf(log.latencies_ms(log.in_window())[0])


def test_closed_loop_keeps_its_clients_busy_through_the_window():
    srv = FakeServer(service_s=0.002)
    seen = []
    log = loadgen.closed_loop(srv.submit, 8, np.arange(8), outstanding=4,
                              warm_requests=8, seconds=0.2, grace_s=5.0,
                              on_window=lambda t0, t1: seen.append((t0, t1)))
    srv.join()
    assert len(seen) == 1 and math.isclose(seen[0][1] - seen[0][0], 0.2)
    sent, done = np.asarray(log.sent), np.asarray(log.done)
    # answers came back all through the window
    assert log.completed_in_window(1) >= 20
    # each answer in the window was followed at once by the next request,
    # so the four clients never fell behind, and never more than four
    # requests were outstanding
    back = int(((done >= log.t0) & (done < log.t1)).sum())
    out = int(((sent >= log.t0) & (sent < log.t1)).sum())
    assert abs(out - back) <= 4
    assert all(((sent <= t) & (done > t)).sum() <= 4 for t in sent)
    assert all(o is not None for o in log.out)
    # each answer is its own payload's
    assert all(float(o[0, 0]) == p for o, p in zip(log.out, log.payload))


def test_open_loop_window_parts_lie_inside_the_window(tmp_path):
    """The open loop names its window before its warm phase. The watch
    waits for the window to open, so the untraced host part, the part with
    the program's spans and the part the profiler records all lie inside
    it, in that order."""
    import run
    # a run has imported JAX and the program (and its spans) long before
    import jax
    from repro import obs  # noqa: F401
    srv = FakeServer(service_s=0.001)
    metrics = types.SimpleNamespace(frames_served=0, slots=0, batches=0)
    watch = run.WindowWatch(metrics, str(tmp_path), trace_s=0.1,
                            devices=jax.devices()[:1])
    log = loadgen.open_loop(srv.submit, 8, np.arange(8),
                            warm_gaps=np.full(60, 0.005),
                            gaps=np.full(400, 0.005), grace_s=5.0,
                            on_window=watch.start)
    watch.join()
    srv.join()
    t = watch.times
    span = log.t1 - log.t0
    assert log.t0 <= t["t0"] < log.t0 + 0.05
    assert log.t0 + run.HOST_SHARE * span <= t["host1"]
    assert t["host1"] + run.SPAN_SHARE * span - 0.05 <= t["spans1"]
    assert t["spans1"] <= t["trace0"] < t["trace1"]
    assert t["trace1"] <= log.t1 + 0.05 <= t["t1"] + 0.05
    assert watch.part("t0", "host1")["seconds"] >= 0.75
