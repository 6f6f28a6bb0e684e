"""The serving runtime: a multi-program router + async micro-batching
scheduler over a pool of device-bound :class:`repro.Executable`\\ s.

Architecture (scheduler + N device workers + completer, plus callers)::

    submit() ──> per-program FIFO queues ──> scheduler ──placement──┐
    (any thread;   bounded: admission         (collect, shed,       │
     returns a      control + back-            pad to bucket)       v
     Future)        pressure)              per-device queues + workers
                                            (steal when idle; device-
                                             bound exe; double-buffered)
                                                       │
                                   shared done queue ──┴──> completer
                                                            (split,
                                                             fulfill,
                                                             metrics)

* **Micro-batching** — the scheduler picks the program whose head request
  is oldest, then holds the batch open up to ``max_wait_ms`` (measured
  from that head request's arrival) or until ``max_batch`` frames are
  collected, whichever comes first. The batch is padded to the nearest
  compiled bucket and executed with *per-frame* CRC calibration
  (``Executable.run_padded``), which makes coalescing and padding
  provably invisible to every request: results are bit-identical to
  per-request ``Executable.run`` calls.
* **Device pool** — ``ServeConfig(devices=N)`` warms one device-bound
  view of every hosted executable per local device
  (``Executable.bind``); closed batches are placed by a pluggable policy
  (least-loaded with rotating ties by default) onto per-device queues,
  idle workers steal from backlogged peers, and each worker overlaps its
  device wait with the next dispatch (``max_inflight`` is the per-device
  pipeline depth). Per-frame calibration makes device placement exactly
  as invisible as padding — see ``serve.pool``.
* **Admission control + backpressure** — the total queued frame count is
  bounded by ``max_queue``: ``submit(block=False)`` raises
  :class:`AdmissionError` when full, ``block=True`` (default) applies
  backpressure to the producer instead.
* **Deadline shedding** — a request carrying ``deadline_ms`` that is
  already past due when its batch is formed is dropped with
  :class:`DeadlineExceeded` instead of burning device time on a result
  nobody is waiting for.
* **Test seams** — every timestamp and timed wait goes through an
  injectable :class:`~repro.serve.clock.Clock` (a
  :class:`~repro.serve.clock.VirtualClock` makes the timing tests
  deterministic), and :class:`Hooks` exposes the batch-close decision
  and the device execute call (fault injection, emulated devices).

Thread-safety notes: the kernel backend/interpret pins are per-thread
(``kernels.dispatch``), so a pool worker pinning an Executable's backend
cannot clobber concurrent callers; all metrics are lock-guarded.
"""

from __future__ import annotations

import dataclasses
import itertools
import queue as queue_mod
import threading
from collections import deque
from concurrent.futures import Future, InvalidStateError
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core.program import Executable, Options, Program
from repro.obs.slo import SLO, SLOMonitor
from repro.serve import batcher, pool as pool_mod
from repro.serve.clock import Clock
from repro.serve.metrics import ProgramMetrics, now

# Chrome-trace lane ids for per-request timelines: each request's
# queue-wait -> batch-assembly -> device -> split spans are recorded
# retrospectively (their life crosses three threads), so they go on a
# synthetic per-request lane instead of overlapping any live thread's
# span stack (see obs.trace).
_REQ_LANE_BASE = 1 << 20


class AdmissionError(RuntimeError):
    """The bounded request queue is full (non-blocking submit, or the
    blocking wait timed out)."""


class DeadlineExceeded(RuntimeError):
    """The request's deadline passed before the device got to it."""


class ServerClosed(RuntimeError):
    """The server is stopped (or stopping) and not accepting work."""


@dataclasses.dataclass
class Hooks:
    """Injectable observation/override points for tests and benchmarks.

    ``batch_close``  called by the scheduler the moment a micro-batch
                     stops collecting, with ``(program, reason, frames)``
                     where reason is one of ``"full"`` (hit the batch
                     cap), ``"speculative"`` (a device was idle),
                     ``"window"`` (``max_wait_ms`` elapsed) or ``"stop"``
                     (server draining). Lets tests assert *why* a batch
                     closed instead of racing wall-clock timings.
    ``execute``      wraps every device execution: called as
                     ``execute(program, device, frames, bucket, default)``
                     where ``default()`` runs the real device-bound
                     executable. Return a result array to substitute it,
                     call ``default()`` to pass through, or raise to
                     fault-inject exactly that batch (the pool converts
                     it to a typed :class:`~repro.serve.pool.WorkerError`
                     on just that batch's requests).
    """

    batch_close: Optional[Callable[[str, str, int], None]] = None
    execute: Optional[Callable] = None


@dataclasses.dataclass
class ServeConfig:
    """Scheduler/queue knobs for a :class:`Server`.

    ``max_batch``      largest device batch a micro-batch may collect (and
                       the top of the default bucket ladder).
    ``max_wait_ms``    how long the scheduler holds a batch open for more
                       requests, measured from its oldest request's
                       arrival. 0 dispatches every request immediately.
    ``max_queue``      admission bound, in *frames*, summed across all
                       hosted programs.
    ``max_inflight``   per-device pipeline depth: batches dispatched to
                       one device but not yet completed (>= 2 overlaps
                       the device wait with the next dispatch; 1 runs
                       each device synchronously).
    ``batch_buckets``  default compiled batch sizes per program (``None``:
                       powers of two up to ``max_batch``).
    ``default_deadline_ms``  deadline applied to requests that don't carry
                       their own (``None``: no deadline).
    ``speculative_close``  dispatch a collecting batch as soon as the queue
                       is drained and some device is idle, instead of
                       waiting out ``max_wait_ms`` — the hold-open window
                       only helps while every device is busy, so on an
                       idle pool it is pure added latency
                       (``batcher.should_close_early``).
    ``devices``        device-pool width: warm one bound executable per
                       local device and fan batches out across them
                       (``None``/1 = single device, exactly the PR-5
                       runtime). Validated against the actual local
                       device count at :meth:`Server.start`.
    ``placement``      placement policy name (``"least_loaded"`` or
                       ``"round_robin"``; see ``serve.pool.PLACEMENTS``).
                       A policy *object* can be injected via
                       ``Server(placement=...)``.
    ``admin_port``     serve the ops endpoint (``/metrics`` ``/healthz``
                       ``/readyz`` ``/statusz`` ``/tracez`` — see
                       ``serve.admin``) on this port for the server's
                       lifetime. ``0`` binds an ephemeral port (read it
                       from ``Server.admin.port``); ``None`` (default)
                       disables the endpoint.
    ``admin_host``     bind address for the ops endpoint (loopback by
                       default — fleet schedulers probe via a sidecar).
    ``log_path``       structured JSON-lines event log destination
                       (``None``: in-memory tail only; see ``obs.log``).
    ``flight_dump_dir``  directory for automatically triggered flight-
                       recorder dumps (SLO breach / worker failure /
                       stop-timeout stranding). ``None`` keeps dumps
                       in-memory only (``Server.flight_dumps()``).
    ``flight_dump_interval_s``  rate limit between automatic dumps — a
                       sustained breach must not turn the black box into
                       a disk firehose; suppressed triggers are counted.
    ``flight_dump_keep``  how many dumps the in-memory ring retains.
    """

    max_batch: int = 8
    max_wait_ms: float = 2.0
    max_queue: int = 256
    max_inflight: int = 2
    batch_buckets: Optional[Tuple[int, ...]] = None
    default_deadline_ms: Optional[float] = None
    speculative_close: bool = True
    devices: Optional[int] = None
    placement: str = "least_loaded"
    admin_port: Optional[int] = None
    admin_host: str = "127.0.0.1"
    log_path: Optional[str] = None
    flight_dump_dir: Optional[str] = None
    flight_dump_interval_s: float = 30.0
    flight_dump_keep: int = 4

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_wait_ms < 0:
            raise ValueError(
                f"max_wait_ms must be >= 0, got {self.max_wait_ms}")
        if self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {self.max_queue}")
        if self.max_inflight < 1:
            raise ValueError(
                f"max_inflight must be >= 1, got {self.max_inflight}")
        if self.devices is not None and self.devices < 1:
            raise ValueError(f"devices must be >= 1, got {self.devices}")
        if self.placement not in pool_mod.PLACEMENTS:
            raise ValueError(
                f"unknown placement {self.placement!r}; known: "
                f"{sorted(pool_mod.PLACEMENTS)}")
        if self.admin_port is not None and not (0 <= self.admin_port <= 65535):
            raise ValueError(
                f"admin_port must be in [0, 65535], got {self.admin_port}")
        if self.flight_dump_interval_s < 0:
            raise ValueError(
                f"flight_dump_interval_s must be >= 0, got "
                f"{self.flight_dump_interval_s}")
        if self.flight_dump_keep < 1:
            raise ValueError(
                f"flight_dump_keep must be >= 1, got {self.flight_dump_keep}")


@dataclasses.dataclass
class _Request:
    frames: np.ndarray                # [n, H, W, C]
    n: int
    future: Future
    t_submit: float
    deadline: Optional[float]         # absolute, server-clock seconds
    trace_id: str = ""                # per-request id, spans every thread
    seq: int = 0                      # request ordinal (trace lane id)


@dataclasses.dataclass
class HostedProgram:
    """One program slot in the router: executable + queue + metrics.

    ``bound`` is the pool's view: one executable per device. With one
    device it is the original (unbound) executable — byte-for-byte the
    PR-5 single-device path, ``Options(shard_batch=True)`` included;
    with N devices each entry is an ``Executable.bind(device)`` view
    sharing the same compiled plan.
    """

    name: str
    program: Program
    executable: Executable
    buckets: Tuple[int, ...]
    queue: deque = dataclasses.field(default_factory=deque)
    metrics: ProgramMetrics = dataclasses.field(default_factory=ProgramMetrics)
    bound: Tuple[Executable, ...] = ()
    slo: Optional[SLOMonitor] = None  # rolling-window objectives (obs.slo)

    @property
    def queued_frames(self) -> int:
        return self.metrics.queued_frames


_SENTINEL = object()
_UNSET = object()


def _settle(future: Future, result=_UNSET,
            exc: Optional[BaseException] = None) -> bool:
    """Resolve ``future`` exactly once; False if it was already settled.

    A timed-out :meth:`Server.stop` fails stranded batches from the
    caller's thread while a wedged worker may still complete them and
    route a late ``Done`` through the completer — both sides settle
    through here so whichever runs second is a recorded no-op instead of
    an ``InvalidStateError`` crash (and metrics only count the winner).
    """
    try:
        if exc is not None:
            future.set_exception(exc)
        else:
            future.set_result(result)
        return True
    except InvalidStateError:
        return False


class Server:
    """Long-lived multi-program serving runtime (see module docstring).

    Usage::

        server = serve.Server(serve.ServeConfig(max_batch=16, devices=4))
        server.register("edge", repro.Program.from_pipeline("edge_detect",
                                                            64, 64, 3),
                        repro.Options(backend="reference"))
        server.register("lenet", repro.Program.from_model("lenet"))
        server.start()                        # warms every device x bucket
        fut = server.submit("edge", frame)    # concurrent.futures.Future
        edges = fut.result()
        print(server.stats()["programs"]["edge"]["latency_ms"])
        server.stop()

    ``Server`` is also a context manager (``with serve.Server(...) as s:``
    starts on enter, drains and stops on exit). Futures resolve to numpy
    arrays; asyncio callers wrap them with ``asyncio.wrap_future``.

    ``clock``, ``hooks`` and ``placement`` are test/bench seams: an
    injectable time source (:class:`~repro.serve.clock.VirtualClock`),
    batch-close/execute hooks (:class:`Hooks`), and a placement policy
    object overriding ``config.placement``.
    """

    def __init__(self, config: Optional[ServeConfig] = None, *,
                 clock: Optional[Clock] = None,
                 hooks: Optional[Hooks] = None,
                 placement=None):
        self.config = config or ServeConfig()
        self._clock = clock or Clock()
        self._hooks = hooks or Hooks()
        self._ndev = self.config.devices or 1
        self._placement = (placement if placement is not None
                           else pool_mod.PLACEMENTS[self.config.placement]())
        self._programs: Dict[str, HostedProgram] = {}
        self._cond = threading.Condition()
        self._queued_total = 0                 # frames across all programs
        self._active_batches = 0               # dispatched, not yet completed
        self._stopping = False
        self._drain = True
        self._started = False
        self._warmed = False
        self._scheduler: Optional[threading.Thread] = None
        self._completer: Optional[threading.Thread] = None
        self._pool: Optional[pool_mod.Pool] = None
        self._done: queue_mod.Queue = queue_mod.Queue()
        self._req_seq = itertools.count()
        self.log = obs.StructuredLog(path=self.config.log_path)
        self.admin = None                      # serve.admin.AdminServer
        # automatic flight-dump state (SLO breach / worker failure /
        # stop-timeout): rate-limited, in-memory ring + optional files
        self._dump_lock = threading.Lock()
        self._flight_dumps: deque = deque(maxlen=self.config.flight_dump_keep)
        self._last_dump_t: Optional[float] = None
        self._dump_seq = 0
        self._dumps_suppressed = 0
        self._last_dump_reason: Optional[str] = None

    # -- lifecycle ---------------------------------------------------------

    def register(self, name: str, program: Program,
                 options: Optional[Options] = None,
                 buckets: Optional[Sequence[int]] = None,
                 slo: Optional[SLO] = None) -> HostedProgram:
        """Host ``program`` under ``name``: compiles it now (plan-cache
        priming happens at registration, jit warm-up at :meth:`start`).

        ``slo`` declares rolling-window objectives for this program
        (:class:`obs.SLO`); a breach increments ``slo.breach.<name>``,
        logs a structured event and triggers a rate-limited flight dump.
        """
        if self._started:
            raise RuntimeError("register() before start()")
        if name in self._programs:
            raise ValueError(f"program {name!r} already registered")
        exe = program.compile(options or Options())
        bks = tuple(sorted({int(b) for b in buckets})) if buckets else \
            (self.config.batch_buckets
             or batcher.power_of_two_buckets(self.config.max_batch))
        if min(bks) < 1:
            raise ValueError(f"buckets must be >= 1, got {bks}")
        hosted = HostedProgram(name, program, exe, bks,
                               metrics=ProgramMetrics(name=name),
                               slo=SLOMonitor(name, slo) if slo else None)
        self._programs[name] = hosted
        return hosted

    def start(self, warm: bool = True) -> "Server":
        """Launch the device pool + scheduler/completer threads.

        Binds every hosted executable to each pool device
        (``Executable.bind`` — shared compiled plan, per-device placement
        caches and donated/reused buffers where safe) and, with ``warm``,
        pre-traces every (device, bucket) pair so the first real requests
        don't pay jit latency — the plan-cache/trace priming a production
        rollout does before taking traffic.
        """
        if self._started:
            raise RuntimeError("server already started")
        if not self._programs:
            raise RuntimeError("no programs registered")
        if self._ndev > 1:
            import jax
            local = jax.local_devices()
            if self._ndev > len(local):
                raise ValueError(
                    f"devices={self._ndev} but only {len(local)} local "
                    f"device(s); on CPU set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={self._ndev}")
            for hosted in self._programs.values():
                # staging ring depth matches the per-device pipeline: a
                # worker may have max_inflight batches dispatched but
                # unawaited, each still reading its staging buffer
                hosted.bound = tuple(
                    hosted.executable.bind(
                        d, staging_slots=max(2, self.config.max_inflight))
                    for d in local[:self._ndev])
        else:
            # single device: keep the *unbound* executable, preserving
            # the exact PR-5 path (Options(shard_batch=True) included)
            for hosted in self._programs.values():
                hosted.bound = (hosted.executable,)
        if warm:
            for hosted in self._programs.values():
                for exe in hosted.bound:
                    exe.warm(hosted.buckets)
        self._warmed = warm
        self._pool = pool_mod.Pool(
            self._ndev, self._placement, self._done, clock=self._clock,
            execute_hook=self._hooks.execute,
            pipeline=self.config.max_inflight)
        self._started = True
        self._scheduler = threading.Thread(
            target=self._scheduler_loop, name="repro-serve-scheduler",
            daemon=True)
        self._completer = threading.Thread(
            target=self._completer_loop, name="repro-serve-completer",
            daemon=True)
        self._pool.start()
        self._completer.start()
        self._scheduler.start()
        if self.config.admin_port is not None:
            from repro.serve.admin import AdminServer
            self.admin = AdminServer(self, port=self.config.admin_port,
                                     host=self.config.admin_host).start()
        self.log.info("serve.start", devices=self._ndev,
                      programs=sorted(self._programs),
                      admin_port=self.admin.port if self.admin else None)
        return self

    def stop(self, drain: bool = True, timeout: Optional[float] = None) -> None:
        """Stop the server. ``drain=True`` serves everything already
        queued first; ``drain=False`` fails pending requests with
        :class:`ServerClosed`. A finite ``timeout`` bounds every join:
        batches a wedged device still holds when it expires are failed
        with :class:`ServerClosed` rather than left stranded (no caller
        blocks forever on ``result()``)."""
        with self._cond:
            self._stopping = True
            self._drain = drain
            self._cond.notify_all()
        if self._scheduler is not None:
            self._scheduler.join(timeout)
            if not self._scheduler.is_alive():
                # retire the pool only once the scheduler can no longer
                # dispatch; when every worker joins, Pool.stop guarantees
                # every dispatched batch's completion is on the done
                # queue before returning, so the sentinel cannot overtake
                # a live completion and strand its futures unresolved. A
                # finite timeout voids that guarantee — reclaim whatever
                # a still-running worker holds and fail it (idempotently:
                # the worker may yet complete an in-flight batch) before
                # the sentinel retires the completer.
                if self._pool is not None:
                    self._pool.stop(timeout)
                    if self._pool.alive():
                        self._fail_stranded()
                self._done.put(_SENTINEL)
                if self._completer is not None:
                    self._completer.join(timeout)
        if not drain:
            with self._cond:
                for hosted in self._programs.values():
                    while hosted.queue:
                        req = hosted.queue.popleft()
                        hosted.metrics.add_queued(-req.n)
                        self._queued_total -= req.n
                        if _settle(req.future,
                                   exc=ServerClosed("server stopped")):
                            hosted.metrics.record_failed()
                self._cond.notify_all()    # release backpressured submitters
        # the ops endpoint outlives the serving threads so a probe during
        # shutdown sees "unhealthy", then goes down last
        if self.admin is not None:
            self.admin.stop(timeout)
        self.log.info("serve.stop", drain=drain)

    def _fail_stranded(self) -> None:
        """Fail every batch a timed-out pool shutdown left behind.

        Queued batches were removed from the worker queues (they can
        never reach the done queue); in-flight batches may still finish
        on the wedged worker, so both sides settle each future through
        :func:`_settle` and only the winner is counted in metrics.
        """
        queued, inflight = self._pool.take_outstanding()
        for batch in queued + inflight:
            failed = sum(
                1 for req in batch.live
                if _settle(req.future, exc=ServerClosed(
                    f"server stopped before the pool drained (stop "
                    f"timeout expired with a batch of "
                    f"{batch.hosted.name!r} outstanding)")))
            if failed:
                batch.hosted.metrics.record_failed(failed)
        if queued or inflight:
            self.log.error("serve.stop.stranded",
                           queued=len(queued), inflight=len(inflight))
            self._flight_dump("stop_timeout")
        if queued:
            # queued batches produce no Done, so the completer will never
            # run its active-batch decrement for them
            with self._cond:
                self._active_batches -= len(queued)
                self._cond.notify_all()

    def __enter__(self) -> "Server":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop(drain=True)

    # -- request path ------------------------------------------------------

    def submit(self, name: str, frames, deadline_ms: Optional[float] = None,
               block: bool = True, timeout: Optional[float] = None) -> Future:
        """Enqueue ``frames`` ([H, W, C] or [n, H, W, C]) for ``name``.

        Returns a ``concurrent.futures.Future`` resolving to the program's
        output for exactly those frames (numpy, batch-first) — bit-identical
        to a direct per-request ``Executable.run``. Raises
        :class:`AdmissionError` when the bounded queue is full
        (``block=False``, or the backpressure wait exceeds ``timeout``),
        :class:`ServerClosed` after :meth:`stop`, and ``ValueError`` for an
        unknown program or a frame-shape mismatch — all in the caller's
        thread, before anything is queued.
        """
        hosted = self._programs.get(name)
        if hosted is None:
            raise ValueError(f"unknown program {name!r}; hosted: "
                             f"{sorted(self._programs)}")
        frames = np.asarray(frames, np.float32)
        if frames.ndim == 3:
            frames = frames[None]
        hwc = tuple(hosted.program.input_hwc)
        if frames.ndim != 4 or tuple(frames.shape[1:]) != hwc:
            raise ValueError(
                f"frames {frames.shape} do not match {name!r}'s input "
                f"[n, {', '.join(map(str, hwc))}]")
        n = frames.shape[0]
        if n == 0:
            raise ValueError("request carries no frames")
        if n > self.config.max_queue:
            # larger than the whole admission bound: the blocking wait
            # below could never be satisfied — fail fast instead
            raise ValueError(
                f"request of {n} frames exceeds max_queue="
                f"{self.config.max_queue}; raise the bound or split the "
                f"request")
        if deadline_ms is None:
            deadline_ms = self.config.default_deadline_ms
        t_submit = self._clock.now()
        seq = next(self._req_seq)
        req = _Request(frames, n, Future(), t_submit,
                       t_submit + deadline_ms / 1e3
                       if deadline_ms is not None else None,
                       trace_id=f"{name}/req-{seq}", seq=seq)
        if obs.recording():
            obs.event("serve.submit", attrs={"program": name, "frames": n},
                      trace_id=req.trace_id)
        with self._cond:
            while (self._queued_total + n > self.config.max_queue
                   and not self._stopping):
                if not block:
                    hosted.metrics.record_reject()
                    raise AdmissionError(
                        f"queue full ({self._queued_total} frames >= "
                        f"{self.config.max_queue})")
                if not self._clock.wait(self._cond, timeout):
                    hosted.metrics.record_reject()
                    raise AdmissionError(
                        f"queue full after {timeout}s backpressure wait")
            if self._stopping:
                raise ServerClosed("server is stopping")
            hosted.queue.append(req)
            hosted.metrics.add_queued(n)
            self._queued_total += n
            hosted.metrics.record_admit()
            self._cond.notify_all()
        return req.future

    # -- scheduler ---------------------------------------------------------

    def _collect(self) -> Optional[Tuple[HostedProgram, list, str, int]]:
        """One scheduling decision: pick a program, hold the batch open,
        pop it. Returns (hosted, requests, close_reason, t_found_ns), the
        last the ``now_ns()`` at which the backlog was found, or None when
        stopping with nothing left to drain."""
        cfg = self.config
        with self._cond:
            while True:
                if self._stopping and not self._drain:
                    return None
                backlog = [h for h in self._programs.values() if h.queue]
                if backlog:
                    break
                if self._stopping:
                    return None
                self._cond.wait()
            t_found = obs.now_ns()
            # route: the program whose head request has waited longest
            hosted = min(backlog, key=lambda h: h.queue[0].t_submit)
            cap = min(cfg.max_batch, max(hosted.buckets))
            close_at = hosted.queue[0].t_submit + cfg.max_wait_ms / 1e3
            reason = None
            while (hosted.metrics.queued_frames < cap
                   and not self._stopping):
                # speculative close: with an idle device in the pool,
                # waiting for more frames is pure added latency —
                # dispatch what we have
                if batcher.should_close_early(hosted.metrics.queued_frames,
                                              cap, self._active_batches,
                                              cfg.speculative_close,
                                              devices=self._ndev):
                    reason = "speculative"
                    break
                remaining = close_at - self._clock.now()
                if remaining <= 0:
                    reason = "window"
                    break
                self._clock.wait(self._cond, remaining)
            if reason is None:
                reason = ("full" if hosted.metrics.queued_frames >= cap
                          else "stop")
            reqs, n = [], 0
            while hosted.queue and n + hosted.queue[0].n <= cap:
                req = hosted.queue.popleft()
                reqs.append(req)
                n += req.n
            if not reqs and hosted.queue:
                # head request alone exceeds the cap: dispatch it solo
                # (run_padded chunks it through the largest bucket)
                reqs = [hosted.queue.popleft()]
                n = reqs[0].n
            hosted.metrics.add_queued(-n)
            self._queued_total -= n
            self._cond.notify_all()        # wake backpressured submitters
        return hosted, reqs, reason, t_found

    def _scheduler_loop(self) -> None:
        while True:
            picked = self._collect()
            if picked is None:
                return
            hosted, reqs, reason, t_found = picked
            t_closed = self._clock.now()   # batch stopped collecting here
            t_closed_ns = obs.now_ns()
            if self._hooks.batch_close is not None:
                self._hooks.batch_close(hosted.name, reason,
                                        sum(r.n for r in reqs))
            # deadline shedding: drop what is already past due
            t = self._clock.now()
            live = []
            for req in reqs:
                if req.deadline is not None and t > req.deadline:
                    # both sides of every settle race go through _settle
                    # (a timed-out stop() or an external cancel may have
                    # resolved this future already); metrics count only
                    # the winner
                    if _settle(req.future, exc=DeadlineExceeded(
                            f"deadline missed by "
                            f"{(t - req.deadline) * 1e3:.1f}ms "
                            f"waiting for dispatch")):
                        hosted.metrics.record_shed()
                        self._observe_slo(hosted, "shed", t)
                else:
                    live.append(req)
            if not live:
                continue
            frames = (live[0].frames if len(live) == 1
                      else np.concatenate([r.frames for r in live], axis=0))
            bucket = batcher.pick_bucket(frames.shape[0], hosted.buckets)
            with self._cond:
                self._active_batches += 1      # a device busy until done
            # hand off to the pool without touching the device: placement
            # picks a worker, the worker dispatches + blocks, and the
            # completer resolves futures off the shared done queue
            device = self._pool.dispatch(pool_mod.Batch(
                hosted, live, frames, bucket, frames.shape[0], t_closed))
            obs.span_ns("serve.batch.collect", t_found, t_closed_ns,
                        device=device, bucket=bucket,
                        frames=frames.shape[0])

    def _completer_loop(self) -> None:
        while True:
            item = self._done.get()
            if item is _SENTINEL:
                return
            t_take = obs.now_ns()
            batch, live, hosted = item.batch, item.batch.live, item.batch.hosted
            try:
                if item.error is not None:
                    failed = sum(1 for req in live
                                 if _settle(req.future, exc=item.error))
                    if failed:
                        hosted.metrics.record_failed(failed)
                    t_fail = self._clock.now()
                    for _ in range(failed):
                        self._observe_slo(hosted, "failed", t_fail)
                    self.log.error(
                        "serve.worker.failure", program=hosted.name,
                        device=item.device, requests=failed,
                        error=str(item.error))
                    # a worker failure is exactly the incident the black
                    # box exists for: capture the moments before it
                    self._flight_dump(f"worker_error:{hosted.name}")
                    continue
                hosted.metrics.record_batch(
                    batcher.padded_slots(batch.n, batch.bucket),
                    batch.t_dispatch, frames=batch.n)
                for part, req in zip(
                        batcher.split_results(item.out, [r.n for r in live]),
                        live):
                    if req.future.done():
                        # a timed-out stop() already failed this request;
                        # the late completion is a no-op, not a crash
                        continue
                    if obs.recording():
                        # recorded before the result is visible: a caller
                        # that reacts to it finds its timeline recorded
                        self._emit_request_timeline(
                            hosted, req, batch.bucket, item.device,
                            batch.t_closed, batch.t_dispatch, item.t_ready,
                            self._clock.now())
                    if not _settle(req.future, result=part):
                        continue
                    t_done = self._clock.now()
                    hosted.metrics.record_served(t_done - req.t_submit, req.n,
                                                 t_done)
                    self._observe_slo(hosted, "served", t_done,
                                      latency_ms=(t_done - req.t_submit) * 1e3)
            finally:
                # a device is idle again: wake a scheduler holding a batch
                # open (speculative close) and any backpressured submitters
                with self._cond:
                    self._active_batches -= 1
                    self._cond.notify_all()
                obs.span_ns("serve.batch.complete", t_take, obs.now_ns(),
                            device=item.device, bucket=batch.bucket,
                            frames=batch.n)

    @staticmethod
    def _emit_request_timeline(hosted: HostedProgram, req: _Request,
                               bucket: int, device: int, t_closed: float,
                               t_dispatch: float, t_ready: float,
                               t_done: float) -> None:
        """Stitch one request's end-to-end latency decomposition into the
        trace: queue-wait -> batch-assembly -> device -> split, all
        carrying the request's ``trace_id`` on its own synthetic lane, so
        the exported Chrome trace shows one contiguous row per request
        even though the spans were measured on three different threads.
        The device phase carries the pool device index that executed it.
        """
        lane = _REQ_LANE_BASE + req.seq
        attrs = {"program": hosted.name, "frames": req.n, "bucket": bucket,
                 "device": device}
        for name, t0, t1 in (
                ("serve.request.queue_wait", req.t_submit, t_closed),
                ("serve.request.batch_assembly", t_closed, t_dispatch),
                ("serve.request.device", t_dispatch, t_ready),
                ("serve.request.split", t_ready, t_done)):
            obs.span_at(name, t0, t1, attrs=attrs, trace_id=req.trace_id,
                        lane_tid=lane, lane=req.trace_id)

    # -- SLOs + incident capture -------------------------------------------

    def _observe_slo(self, hosted: HostedProgram, kind: str, t: float,
                     latency_ms: Optional[float] = None) -> None:
        """Feed one request outcome to the program's SLO monitor (if
        any); every breach report the evaluation returns is handled."""
        if hosted.slo is None:
            return
        for breach in hosted.slo.observe(kind, t, latency_ms=latency_ms):
            self._handle_breach(hosted, breach)

    def _handle_breach(self, hosted: HostedProgram, breach: Dict) -> None:
        """One SLO breach: counter + structured log + flight dump."""
        obs.counter(f"slo.breach.{hosted.name}").inc()
        obs.event("serve.slo.breach",
                  attrs={"program": hosted.name, **breach})
        self.log.warning("serve.slo.breach", program=hosted.name, **breach)
        self._flight_dump(
            f"slo:{hosted.name}:{breach['objective']}", detail=breach)

    def _flight_dump(self, reason: str,
                     detail: Optional[Dict] = None) -> Optional[Dict]:
        """Dump the flight recorder, rate-limited by
        ``config.flight_dump_interval_s``. Returns the dump dict, or
        None when no recorder is installed / the limiter suppressed it.

        The ``flight.trigger`` instant event is recorded *before* the
        dump so the dump itself proves where in the retained history the
        incident sits (``check_trace.py --flight`` requires spans from
        before the trigger).
        """
        fl = obs.get_flight()
        if fl is None:
            return None
        t = self._clock.now()
        with self._dump_lock:
            if (self._last_dump_t is not None
                    and t - self._last_dump_t
                    < self.config.flight_dump_interval_s):
                self._dumps_suppressed = self._dumps_suppressed + 1
                return None
            self._last_dump_t = t
            self._last_dump_reason = reason
            self._dump_seq = self._dump_seq + 1
            seq = self._dump_seq
        obs.event("flight.trigger", attrs={"reason": reason,
                                           **(detail or {})})
        dump = fl.dump(reason=reason)
        path = None
        if self.config.flight_dump_dir is not None:
            import json as json_mod
            import os
            slug = "".join(c if c.isalnum() else "-" for c in reason)[:48]
            path = os.path.join(self.config.flight_dump_dir,
                                f"flight-{seq:03d}-{slug}.json")
            os.makedirs(self.config.flight_dump_dir, exist_ok=True)
            with open(path, "w") as f:
                json_mod.dump(dump, f)
        with self._dump_lock:
            self._flight_dumps.append(
                {"seq": seq, "reason": reason, "t": t, "path": path,
                 "records": dump["otherData"]["records"], "dump": dump})
        self.log.info("serve.flight.dump", reason=reason, path=path,
                      records=dump["otherData"]["records"])
        return dump

    def flight_dumps(self) -> list:
        """The retained automatic dumps, oldest first (metadata + dump)."""
        with self._dump_lock:
            return list(self._flight_dumps)

    # -- health + ops surface ----------------------------------------------

    def health(self) -> Dict[str, object]:
        """The ``/healthz`` answer: is every serving thread running?

        Healthy means started, not stopping, and the pool has *all* its
        workers — a pool that lost one of four devices still serves, but
        a fleet scheduler must know it is degraded.
        """
        pool = self._pool
        with self._cond:
            stopping = self._stopping
        checks = {
            "started": self._started,
            "not_stopping": not stopping,
            "scheduler_alive": (self._scheduler is not None
                                and self._scheduler.is_alive()),
            "completer_alive": (self._completer is not None
                                and self._completer.is_alive()),
            "pool_workers": (pool.workers_alive() if pool is not None else 0),
            "pool_size": pool.size if pool is not None else 0,
        }
        healthy = bool(
            checks["started"] and checks["not_stopping"]
            and checks["scheduler_alive"] and checks["completer_alive"]
            and pool is not None and pool.healthy())
        return {"healthy": healthy, "checks": checks}

    def readiness(self) -> Dict[str, object]:
        """The ``/readyz`` answer: healthy *and* able to take traffic —
        buckets warmed (no jit latency on the next request) and the
        admission queue not already full."""
        h = self.health()
        with self._cond:
            depth = self._queued_total
        checks = {
            "warmed": self._warmed,
            "queue_depth": depth,
            "max_queue": self.config.max_queue,
            "queue_has_room": depth < self.config.max_queue,
        }
        ready = bool(h["healthy"] and checks["warmed"]
                     and checks["queue_has_room"])
        return {"ready": ready, "checks": {**h["checks"], **checks}}

    def prometheus_metrics(self) -> str:
        """Every registry this server touches, in one exposition blob:
        the process-wide ``obs.REGISTRY`` (plan cache, conv dispatch,
        SLO breach counters), each hosted program's private registry and
        the pool's per-device registry."""
        parts = [obs.prometheus_text()]
        for hosted in self._programs.values():
            parts.append(obs.prometheus_text(hosted.metrics.registry))
        if self._pool is not None:
            parts.append(obs.prometheus_text(self._pool.registry))
        return "".join(parts)

    # -- observability -----------------------------------------------------

    def stats(self, verbose: bool = False) -> Dict[str, object]:
        """JSON-able snapshot: per-program counters, latency percentiles,
        achieved frames/s, padding waste, queue depth — plus each program's
        modeled device FPS / power / kFPS-per-W from its compiled report,
        the measured-vs-modeled kFPS/W drift, the process-wide plan-cache
        hit rate, per-strategy conv dispatch counts (``repro.obs``) and
        the device pool's per-device occupancy/steal/failure breakdown
        (``"pool"`` — see ``serve.pool.Pool.stats``).

        ``verbose=True`` adds the batch-occupancy / padding-waste
        histograms per program and the full global ``obs`` registry dump
        — the breakdown ``serve.format_stats`` renders as a table.
        """
        from repro.core.plan import plan_cache_stats
        programs = {}
        totals = {"submitted": 0, "served": 0, "shed_deadline": 0,
                  "rejected": 0, "failed": 0}
        frames_served = 0
        for name, hosted in self._programs.items():
            snap = hosted.metrics.snapshot()
            r = hosted.executable.report
            # modeled energy per frame (J) from the power report: the
            # measured-vs-modeled efficiency axis. "Measured" kFPS/W
            # re-uses the modeled device power with the *achieved* rate —
            # the drift isolates host/scheduling losses from the model.
            e_frame = (r.avg_power_w / r.fps) if r.fps else 0.0
            fps = snap["achieved_fps"]
            measured_kfps_per_w = ((fps / 1e3) / r.avg_power_w
                                   if r.avg_power_w else 0.0)
            snap["model"] = {
                "fps": r.fps, "avg_power_w": r.avg_power_w,
                "kfps_per_w": r.kfps_per_w,
                "energy_per_frame_j": e_frame,
                "modeled_energy_j": e_frame * snap["frames_served"],
            }
            snap["measured_kfps_per_w"] = measured_kfps_per_w
            snap["kfps_per_w_drift"] = (measured_kfps_per_w / r.kfps_per_w
                                        if r.kfps_per_w else 0.0)
            snap["buckets"] = list(hosted.buckets)
            if hosted.slo is not None:
                snap["slo"] = hosted.slo.state(self._clock.now())
            if verbose:
                snap["histograms"] = hosted.metrics.histograms()
            programs[name] = snap
            for k in totals:
                totals[k] += snap["requests"][k]
            frames_served += snap["frames_served"]
        with self._cond:
            depth = self._queued_total
        cache = plan_cache_stats()
        lookups = cache["hits"] + cache["misses"]
        strategies = {
            kind: c.get() for kind in ("resident", "strip", "fused",
                                       "reference")
            if (c := obs.REGISTRY.get(f"dispatch.conv.{kind}")) is not None}
        out = {
            "config": dataclasses.asdict(self.config),
            "queue_depth": depth,
            "frames_served": frames_served,
            "requests": totals,
            "plan_cache": {**cache,
                           "hit_rate": (cache["hits"] / lookups
                                        if lookups else 0.0)},
            "conv_dispatch": strategies,
            "programs": programs,
        }
        if self._pool is not None:
            out["pool"] = self._pool.stats()
        with self._dump_lock:
            out["flight"] = {
                "dumps": self._dump_seq,
                "suppressed": self._dumps_suppressed,
                "last_reason": self._last_dump_reason,
                "retained": [{k: v for k, v in d.items() if k != "dump"}
                             for d in self._flight_dumps],
            }
        fl = obs.get_flight()
        if fl is not None:
            out["flight"]["recorder"] = fl.stats()
        if verbose:
            out["obs"] = obs.REGISTRY.snapshot()
        return out
