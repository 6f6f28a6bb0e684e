"""The device pool: N local devices, one warmed Executable each.

Lightator's efficiency story is fleet-scale — an N-device board behind
one host runtime — but the PR-5 scheduler drove exactly one warmed
``Executable``, so the host saturated long before a multi-device board
would. This module is the missing layer between the scheduler and the
devices::

    scheduler ──placement──> per-device queues ──> worker threads ──┐
                (least-loaded,    (steal when idle)   (dispatch +    │
                 pluggable)                            block, double-│
                                                       buffered)     v
                                            shared completion queue ──> completer

* **Placement** — the scheduler hands each closed micro-batch to
  :meth:`Pool.dispatch`, which asks the placement policy for a device
  index given every worker's current load (queued + in-flight frames).
  The default :class:`LeastLoaded` picks the least-loaded worker and
  rotates ties, so an all-idle pool spreads consecutive batches across
  devices instead of hammering device 0. :class:`RoundRobin` ignores
  load entirely (deterministic placement for tests). Policies are plain
  objects with a ``choose(loads) -> index`` method — inject any via
  ``Server(placement=...)``.
* **Work stealing** — placement is a guess made at dispatch time; loads
  drift while batches wait. A worker whose own queue is empty steals the
  *oldest* batch from the most-backlogged peer before going to sleep, so
  one slow device cannot strand queued work while others idle.
* **Per-device pipelining** — each worker dispatches a batch to its
  device asynchronously, then blocks on the *previous* batch's result
  while the new one computes (``ServeConfig.max_inflight >= 2``; 1 runs
  synchronously). The blocking wait happens on the worker thread, so the
  shared completer never waits on a device — it only splits results and
  resolves futures, and a slow device can never head-of-line-block
  another device's completions.
* **Fault isolation** — an exception from a device worker (or the
  injectable ``Hooks.execute`` seam around it) fails exactly that
  batch's requests with a typed :class:`WorkerError` (original exception
  chained as ``__cause__``); the worker, the pool, and every other batch
  keep running, and the failure is counted per device.

Results are **bit-identical** to single-device execution: every worker
runs the same per-frame-calibrated executor (``Executable.run_padded``)
on a device-bound view of the same compiled plan, and per-frame
calibration makes each frame's result a pure function of that frame —
device placement, batch composition, padding and steal order can never
perturb it (property suite: ``tests/test_serve_pool.py``).
"""

from __future__ import annotations

import dataclasses
import queue as queue_mod
import threading
from collections import deque
from typing import Callable, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.serve.clock import Clock

# Chrome-trace lane ids for per-device execute spans: the execute span is
# recorded retrospectively (dispatch happened one loop iteration before
# the blocking wait returns), so it goes on a synthetic per-device lane
# instead of the worker thread's live span stack.
_DEVICE_LANE_BASE = 1 << 21


class WorkerError(RuntimeError):
    """A device worker failed to execute a batch.

    Exactly the failed batch's requests receive this error (the original
    exception is chained as ``__cause__``); other batches, the worker,
    and the rest of the pool are unaffected. Carries ``program`` and
    ``device`` so callers can tell *where* the batch died.
    """

    def __init__(self, message: str, program: str = "", device: int = -1):
        super().__init__(message)
        self.program = program
        self.device = device


# ---------------------------------------------------------------------------
# Placement policies
# ---------------------------------------------------------------------------

class LeastLoaded:
    """Pick the device with the fewest queued + in-flight frames.

    Ties rotate: the scan starts just past the previous winner, so an
    all-idle pool (every load 0 — the common case at low offered load)
    spreads consecutive batches round-robin instead of always choosing
    device 0. Strictly-lower load always wins regardless of rotation.
    """

    def __init__(self):
        self._start = 0

    def choose(self, loads: Sequence[int]) -> int:
        n = len(loads)
        best, best_load = None, None
        for k in range(n):
            i = (self._start + k) % n
            if best_load is None or loads[i] < best_load:
                best, best_load = i, loads[i]
        self._start = (best + 1) % n
        return best


class RoundRobin:
    """Strict rotation, load-blind — deterministic placement for tests."""

    def __init__(self):
        self._next = 0

    def choose(self, loads: Sequence[int]) -> int:
        i = self._next % len(loads)
        self._next = i + 1
        return i


PLACEMENTS = {"least_loaded": LeastLoaded, "round_robin": RoundRobin}


# ---------------------------------------------------------------------------
# Batch / completion currency between scheduler, workers and completer
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class Batch:
    """One closed micro-batch in flight through the pool.

    Identity semantics (``eq=False``): batches are tracked in per-worker
    in-flight lists and removed by ``is``-equality — field-wise ``==``
    over numpy frames would be both wrong and ambiguous.
    """

    hosted: object                    # serve.server.HostedProgram
    live: list                        # [_Request] whose futures to resolve
    frames: np.ndarray                # [n, H, W, C] concatenated
    bucket: int
    n: int                            # real frames (== frames.shape[0])
    t_closed: float
    t_dispatch: float = 0.0           # stamped by the worker at dispatch


@dataclasses.dataclass
class Done:
    """A finished (or failed) batch, handed to the shared completer."""

    batch: Batch
    device: int
    out: Optional[np.ndarray]         # host-side result (None on error)
    error: Optional[BaseException]
    t_ready: float


_STOP = object()


class _Worker:
    """One device: bound executable index, FIFO queue, metrics, thread."""

    def __init__(self, index: int, registry: obs.Registry):
        self.index = index
        self.queue: deque = deque()
        self.queued_frames = 0
        self.inflight_frames = 0
        self.inflight: List[Batch] = []   # dispatched, not yet completed
        p = f"serve.pool.device{index}"
        self.batches = registry.counter(f"{p}.batches")
        self.frames = registry.counter(f"{p}.frames")
        self.steals = registry.counter(f"{p}.steals")
        self.failures = registry.counter(f"{p}.failures")
        self.busy_s = registry.gauge(f"{p}.busy_s")
        # last completion time on this device (worker-thread private):
        # under dispatch-ahead pipelining a batch is dispatched before
        # its predecessor's results are ready, so its device-busy span
        # starts at max(t_dispatch, predecessor ready) — the device
        # executes serially even when the host runs ahead
        self.last_ready: Optional[float] = None
        self.thread: Optional[threading.Thread] = None

    @property
    def load(self) -> int:
        return self.queued_frames + self.inflight_frames


class Pool:
    """N device workers + placement + a shared completion queue.

    The pool does not know about requests or futures — it moves
    :class:`Batch` objects from :meth:`dispatch` to the ``done`` queue,
    executing each on one device via the hosted program's device-bound
    executable (``hosted.bound[device_index]``). The server's completer
    consumes ``done``.
    """

    def __init__(self, n_devices: int, policy, done: queue_mod.Queue,
                 clock: Optional[Clock] = None, execute_hook:
                 Optional[Callable] = None, pipeline: int = 2):
        if n_devices < 1:
            raise ValueError(f"pool needs >= 1 device, got {n_devices}")
        self.registry = obs.Registry()
        self._policy = policy
        self._done = done
        self._clock = clock or Clock()
        self._execute_hook = execute_hook
        self._pipeline = max(int(pipeline), 1)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._stopping = False
        self._t_start: Optional[float] = None
        self._steals = self.registry.counter("serve.pool.steals")
        self._placement_us = self.registry.histogram(
            "serve.pool.placement_us",
            buckets=(1.0, 5.0, 10.0, 50.0, 100.0, 500.0, 1000.0, 5000.0))
        self._workers: List[_Worker] = [
            _Worker(i, self.registry) for i in range(n_devices)]

    @property
    def size(self) -> int:
        return len(self._workers)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "Pool":
        self._t_start = self._clock.now()
        for w in self._workers:
            w.thread = threading.Thread(
                target=self._run, args=(w,),
                name=f"repro-serve-device{w.index}", daemon=True)
            w.thread.start()
        return self

    def stop(self, timeout: Optional[float] = None) -> None:
        """Drain every queue, flush pending batches, join the workers.

        Every dispatched batch's completion is on the ``done`` queue by
        the time this returns **provided every worker joined** (workers
        enqueue before exiting) — then the caller can safely sentinel
        its completer. Under a finite ``timeout`` a wedged worker may
        outlive the join; check :meth:`alive` and reclaim its work via
        :meth:`take_outstanding` before putting any sentinel, or those
        batches' futures are stranded unresolved.
        """
        with self._cond:
            self._stopping = True
            self._cond.notify_all()
        for w in self._workers:
            if w.thread is not None:
                w.thread.join(timeout)

    def alive(self) -> bool:
        """True while any worker thread is still running (a finite
        ``stop(timeout)`` may return before the pool is quiescent)."""
        return any(w.thread is not None and w.thread.is_alive()
                   for w in self._workers)

    def workers_alive(self) -> int:
        """How many worker threads are currently running."""
        return sum(1 for w in self._workers
                   if w.thread is not None and w.thread.is_alive())

    def healthy(self) -> bool:
        """True only when *every* worker thread is running.

        :meth:`alive` answers "is the pool still doing anything" (the
        stop/drain question); this answers the ``/healthz`` question —
        a pool that lost one of four workers is degraded even though
        it still serves.
        """
        return all(w.thread is not None and w.thread.is_alive()
                   for w in self._workers)

    def take_outstanding(self):
        """Reclaim work a timed-out :meth:`stop` left behind.

        Returns ``(queued, inflight)``: ``queued`` batches are *removed*
        from the worker queues (no worker can pick them up afterwards,
        so they will never reach the ``done`` queue — the caller owns
        failing their futures); ``inflight`` is a snapshot of batches
        dispatched to a device but not yet completed — a wedged worker
        may still complete one later, so the caller must settle their
        futures idempotently.
        """
        queued: List[Batch] = []
        inflight: List[Batch] = []
        with self._cond:
            for w in self._workers:
                while w.queue:
                    batch = w.queue.popleft()
                    w.queued_frames -= batch.n
                    queued.append(batch)
                inflight.extend(w.inflight)
        return queued, inflight

    # -- dispatch (scheduler thread) ---------------------------------------

    def dispatch(self, batch: Batch) -> int:
        """Place ``batch`` on a device queue; returns the device index."""
        t0 = self._clock.now()
        with self._cond:
            idx = self._policy.choose([w.load for w in self._workers])
            w = self._workers[idx]
            w.queue.append(batch)
            w.queued_frames += batch.n
            self._cond.notify_all()
        self._placement_us.observe((self._clock.now() - t0) * 1e6)
        return idx

    # -- worker loop -------------------------------------------------------

    def _next(self, w: _Worker, block: bool):
        """Own queue first, then steal the oldest batch from the most
        backlogged peer; ``_STOP`` when stopping and fully drained, and
        ``None`` when idle but a pending batch still needs finishing
        (``block=False``)."""
        with self._cond:
            while True:
                if w.queue:
                    batch = w.queue.popleft()
                    w.queued_frames -= batch.n
                    return batch
                victim = max((v for v in self._workers if v.queue),
                             key=lambda v: v.queued_frames, default=None)
                if victim is not None:
                    batch = victim.queue.popleft()    # oldest: FIFO-fair
                    victim.queued_frames -= batch.n
                    w.steals.inc()
                    self._steals.inc()
                    if obs.recording():
                        obs.event("serve.pool.steal",
                                  attrs={"thief": w.index,
                                         "victim": victim.index,
                                         "frames": batch.n})
                    return batch
                if self._stopping:
                    return _STOP
                if not block:
                    return None
                self._cond.wait()

    def _run(self, w: _Worker) -> None:
        obs.set_thread_device(w.index)     # this thread is w's lane
        pending = None                 # (batch, lazy device result)
        while True:
            nxt = self._next(w, block=pending is None)
            if nxt is None:            # idle: finish the in-flight batch
                self._finish(w, *pending)
                pending = None
                continue
            if nxt is _STOP:
                if pending is not None:
                    self._finish(w, *pending)
                return
            out = self._dispatch_one(w, nxt)
            if pending is not None:
                self._finish(w, *pending)
                pending = None
            if out is not None:        # dispatch succeeded
                if self._pipeline > 1:
                    pending = (nxt, out)    # overlap wait with next dispatch
                else:
                    self._finish(w, nxt, out)

    def _dispatch_one(self, w: _Worker, batch: Batch):
        """Async-dispatch ``batch`` on this worker's device. Returns the
        lazy device result, or None after routing a failure to ``done``.
        Its ``serve.batch.stage`` span runs from here to the jitted call's
        return, with a ``pad``, ``put`` and ``launch`` span a chunk inside
        it: ``Executable.run_padded``'s steps, timed one by one."""
        t_stage = obs.now_ns()
        batch.t_dispatch = self._clock.now()
        with self._lock:
            w.inflight_frames += batch.n
            w.inflight.append(batch)
        exe = batch.hosted.bound[w.index]
        name = batch.hosted.name

        def default():
            bucket, outs = batch.bucket, []
            t_pad = obs.now_ns()
            for chunk, real in exe.pad_chunks(batch.frames, bucket):
                t_put = obs.now_ns()
                placed = exe.place(chunk)
                t_launch = obs.now_ns()
                out = exe.launch(placed)
                t_end = obs.now_ns()
                obs.span_ns("serve.batch.pad", t_pad, t_put, bucket=bucket,
                            frames=real)
                obs.span_ns("serve.batch.put", t_put, t_launch,
                            bucket=bucket, frames=real)
                obs.span_ns("serve.batch.launch", t_launch, t_end,
                            bucket=bucket, frames=real)
                outs.append(out[:real])
                t_pad = obs.now_ns()
            return outs[0] if len(outs) == 1 else jnp.concatenate(outs)

        try:
            if self._execute_hook is not None:
                return self._execute_hook(name, w.index, batch.frames,
                                          batch.bucket, default)
            return default()
        except Exception as e:          # noqa: BLE001 — isolate the batch
            self._fail(w, batch, e)
            return None
        finally:
            obs.span_ns("serve.batch.stage", t_stage, obs.now_ns(),
                        bucket=batch.bucket, frames=batch.n)

    def _finish(self, w: _Worker, batch: Batch, out) -> None:
        """Block until the device result is ready; hand it to ``done``."""
        t_wait = obs.now_ns()
        try:
            out_np = np.asarray(out)
        except Exception as e:          # noqa: BLE001 — isolate the batch
            self._fail(w, batch, e)
            return
        finally:
            obs.span_ns("serve.batch.wait", t_wait, obs.now_ns(),
                        bucket=batch.bucket, frames=batch.n)
        t_ready = self._clock.now()
        with self._lock:
            w.inflight_frames -= batch.n
            w.inflight.remove(batch)
        # clamp the busy interval to this device's previous completion:
        # a pipelined batch was dispatched while its predecessor still
        # ran, but the device itself is serial — without the clamp the
        # device lane's spans would overlap and busy_s would double-
        # count the overlap (occupancy > 1)
        t_busy0 = batch.t_dispatch
        if w.last_ready is not None and w.last_ready > t_busy0:
            t_busy0 = w.last_ready
        w.last_ready = t_ready
        w.batches.inc()
        w.frames.inc(batch.n)
        w.busy_s.add(t_ready - t_busy0)
        if obs.recording():
            obs.span_at("serve.device.execute", t_busy0, t_ready,
                        attrs={"device": w.index,
                               "program": batch.hosted.name,
                               "bucket": batch.bucket, "frames": batch.n,
                               "queued_ms":
                                   (t_busy0 - batch.t_dispatch) * 1e3},
                        lane_tid=_DEVICE_LANE_BASE + w.index,
                        lane=f"device{w.index}")
        self._done.put(Done(batch, w.index, out_np, None, t_ready))

    def _fail(self, w: _Worker, batch: Batch, exc: BaseException) -> None:
        with self._lock:
            w.inflight_frames -= batch.n
            w.inflight.remove(batch)
        w.failures.inc()
        err = WorkerError(
            f"device {w.index} failed executing a bucket-{batch.bucket} "
            f"batch of {batch.hosted.name!r}: {exc}",
            program=batch.hosted.name, device=w.index)
        err.__cause__ = exc
        if obs.recording():
            obs.event("serve.pool.failure",
                      attrs={"device": w.index,
                             "program": batch.hosted.name,
                             "error": type(exc).__name__})
        self._done.put(Done(batch, w.index, None, err, self._clock.now()))

    # -- observability -----------------------------------------------------

    def stats(self) -> dict:
        """JSON-able pool snapshot for ``Server.stats()``: per-device
        batch/frame/steal/failure counts, in-flight frames, busy seconds
        and occupancy (busy / wall since start), plus pool-wide steal
        count and the placement-latency histogram summary."""
        wall = None
        if self._t_start is not None:
            wall = max(self._clock.now() - self._t_start, 1e-9)
        with self._lock:
            per_device = [{
                "device": w.index,
                "batches": w.batches.get(),
                "frames": w.frames.get(),
                "steals": w.steals.get(),
                "failures": w.failures.get(),
                "queued_frames": w.queued_frames,
                "inflight_frames": w.inflight_frames,
                "busy_s": w.busy_s.get(),
                "occupancy": (w.busy_s.get() / wall if wall else 0.0),
            } for w in self._workers]
        return {
            "devices": len(self._workers),
            "placement": type(self._policy).__name__,
            "pipeline": self._pipeline,
            "steals": self._steals.get(),
            "placement_us": self._placement_us.summary(),
            "per_device": per_device,
        }
