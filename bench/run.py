#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs``) and a traffic mix (``bench/traffic``).
One run, in one process:

1. set-up: the compile cache, the chip checks, weights made on the device
   from ``--seed``, a pool of frames from ``--seed``, the program hosted in
   a ``repro.serve.Server`` on the pallas backend and warmed at the
   traffic's batch buckets, then a short warm phase of the traffic itself;
2. the measured window of ``--seconds``, driven through
   ``Server.submit`` by the traffic's closed or open loop;
3. the check: every answered request's output against the plain reference
   (``bench/reference.py``) run on the same frames after the window.

With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read by ``bench/metrics/<name>.py``
from a profiler trace of part of the window, the server's spans and its
counters. The run fails, printing no result, without a TPU, with fewer
chips than the cell asks for, on a chip missing from ``bench/peaks.json``,
or when the options resolve to anything but the pallas backend compiled
for the chip.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import gc
import json
import shutil
import sys
import tempfile
import threading
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

import cells  # noqa: E402
import check  # noqa: E402
import loadgen  # noqa: E402
import model  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

# a traced run reads its per-layer metrics from three parts of the window:
# its first HOST_SHARE with nothing recorded (the rate that mfu reads, and
# the batch counters); the next SPAN_SHARE with the program's spans
# collected (they allocate enough to bring on collections of up to 100 ms
# over ten seconds, which moved VGG16 from full to part-filled batches);
# then the profiler, which holds the process for seconds as it starts,
# recording TRACE_SECONDS
HOST_SHARE = 0.4
SPAN_SHARE = 0.1
TRACE_SECONDS = 1.0
# the latency a refused, failed or unanswered request reads as
UNANSWERED_MS = 1e12
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def fail(msg: str) -> None:
    say(f"FAIL: {msg}")
    sys.exit(1)


class CompileCounter:
    """Counts JAX traces and compiles, with the host time of each."""

    def __init__(self):
        self.events: List[tuple] = []
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, *args, **kwargs):
        if event in COMPILE_EVENTS:
            self.events.append((loadgen.now(), kwargs.get("fun_name", "")))

    def between(self, t0: float, t1: float) -> List[str]:
        return [name for t, name in list(self.events) if t0 <= t < t1]


class GcWatch:
    """Pauses of Python's cyclic garbage collector, by host time: a pause
    holds every serving thread, the generator's among them."""

    def __init__(self):
        self.pauses: List[tuple] = []         # (start, seconds, generation)
        self._t0: Optional[float] = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = loadgen.now()
        elif self._t0 is not None:
            self.pauses.append((self._t0, loadgen.now() - self._t0,
                                info["generation"]))
            self._t0 = None

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def summary(self, t0: float, t1: float) -> str:
        inside = [(d, g) for t, d, g in self.pauses if t0 <= t < t1]
        by_gen = {g: sum(1 for _, h in inside if h == g) for g in (0, 1, 2)}
        longest = max((d for d, _ in inside), default=0.0) * 1e3
        return (f"gc: {len(inside)} collections in the window {by_gen}, "
                f"the longest {longest:.3f} ms")


class WindowWatch:
    """A thread that follows the window ``[t0, t1)`` and snapshots the
    server's counters at the edges of its parts. In a traced run
    (``trace_dir`` given) the window has three parts that the per-layer
    metrics read, in this order: ``host``, its first ``HOST_SHARE``, with
    nothing recorded; ``spans``, the next ``SPAN_SHARE``, over which the
    program's own spans are collected; and ``trace``, up to ``trace_s``
    seconds recorded by the profiler, which starts once the spans end.
    The profiler stops on a thread of its own, so that the window's end
    is read on time."""

    def __init__(self, metrics, trace_dir: Optional[str], trace_s: float,
                 devices=()):
        self.metrics = metrics
        self.trace_dir = trace_dir
        self.trace_s = trace_s
        self._mark = window_mark(devices) if trace_dir is not None else None
        self.snaps: Dict[str, Dict] = {}
        self.times: Dict[str, float] = {}
        self.spans: List[Dict] = []
        self.error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._stopper: Optional[threading.Thread] = None
        self._profiling = False

    def _snap(self, key: str) -> None:
        m = self.metrics
        self.times[key] = loadgen.now()
        self.snaps[key] = {"frames": m.frames_served, "slots": m.slots,
                           "batches": m.batches}

    def start_profiler(self) -> None:
        import jax
        # the device planes only: the Python tracer slows the serving
        # threads several-fold, and the host tracer (level 1 and up) stops
        # transfers overlapping the device's work, which cut VGG16 from
        # 640 to ~230 frames/s while it recorded
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._profiling = True

    def stop_profiler(self) -> None:
        if self._profiling:
            import jax
            self._profiling = False
            t = loadgen.now()
            jax.profiler.stop_trace()
            say(f"profiler stopped in {loadgen.now() - t:.1f} s")

    def start(self, t0: float, t1: float) -> None:
        """Follow the window ``[t0, t1)``; ``t0`` may lie ahead (the open
        loop names its window before the warm phase)."""
        self._thread = threading.Thread(target=self._run, args=(t0, t1),
                                        name="bench-window", daemon=True)
        self._thread.start()

    def _run(self, t0: float, t1: float) -> None:
        try:
            if self.trace_dir is not None:
                # imported ahead of the window, not inside it
                from repro import obs  # noqa: F401
            time.sleep(max(t0 - loadgen.now(), 0.0))
            self._snap("t0")
            if self.trace_dir is not None:
                self._traced_parts(t0, t1)
            time.sleep(max(t1 - loadgen.now(), 0.0))
            self._snap("t1")
        except Exception as e:  # noqa: BLE001 - reported by the main thread
            self.error = e

    def _traced_parts(self, t0: float, t1: float) -> None:
        from repro import obs
        time.sleep(max(t0 + HOST_SHARE * (t1 - t0) - loadgen.now(), 0.0))
        self._snap("host1")
        otrace = obs.enable()
        try:
            time.sleep(max(t0 + (HOST_SHARE + SPAN_SHARE) * (t1 - t0)
                           - loadgen.now(), 0.0))
            self._snap("spans1")
        finally:
            obs.disable()
        self.spans = otrace.spans()
        self.start_profiler()
        try:
            self._mark()
            self._snap("trace0")
            time.sleep(max(min(self.trace_s, t1 - loadgen.now()), 0.0))
            self._mark()
            self._snap("trace1")
        finally:
            self._stopper = threading.Thread(
                target=self.stop_profiler, name="bench-profiler-stop")
            self._stopper.start()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()
        if self._stopper is not None:
            self._stopper.join()
        self.stop_profiler()
        if self.error is not None:
            raise self.error

    def part(self, a: str, b: str) -> Optional[Dict]:
        """The counters' change from snapshot ``a`` to ``b`` and its
        seconds, or None where the run has no such part."""
        if a not in self.snaps or b not in self.snaps:
            return None
        d = {k: self.snaps[b][k] - self.snaps[a][k] for k in self.snaps[a]}
        d["seconds"] = self.times[b] - self.times[a]
        return d


def window_mark(devices):
    """A callable that runs a tiny program named ``trace_reduce.WINDOW_MARK``
    on each of ``devices`` and waits for it: in a trace of the device
    planes, these runs mark where the traced part opens and closes. It is
    compiled here, in set-up."""
    import jax

    def mark(x):
        return x + 1

    mark.__name__ = trace_reduce.WINDOW_MARK
    fn = jax.jit(mark)
    xs = [jax.device_put(np.zeros(8, np.float32), d) for d in devices]

    def run_marks():
        jax.block_until_ready([fn(x) for x in xs])

    run_marks()
    return run_marks


def percentile_ms(lat: np.ndarray, q: float) -> Optional[float]:
    """Nearest-rank percentile (defined where the tail holds +inf, which
    an interpolating one turns into nan); +inf, which JSON cannot hold,
    reads as UNANSWERED_MS."""
    if len(lat) == 0:
        return None
    v = float(np.percentile(lat, q, method="inverted_cdf"))
    return v if np.isfinite(v) else UNANSWERED_MS


def warm_shapes(hosted, traffic: Dict) -> None:
    """Run every batch size the traffic can close through each device's
    executable, the way the server runs it (``run_padded``): the server's
    own warm-up compiles the buckets, not the slicing of a part-filled
    bucket's results, which would otherwise compile inside the window."""
    from repro.serve import batcher
    per_request = traffic["frames_per_request"]
    cap = traffic["server"]["max_batch"]
    hwc = hosted.program.input_hwc
    for exe in hosted.bound:
        for n in range(per_request, cap + 1, per_request):
            np.asarray(exe.run_padded(np.zeros((n, *hwc), np.float32),
                                      batcher.pick_bucket(n, hosted.buckets)))


def keep(tr: Dict, n_devices: int, path: str) -> None:
    """Write the first 20 ms of the traced window, with what its
    reduction reads, as JSON (a recorded trace for the tests)."""
    part = trace_reduce.cut(tr, 0.02)
    r = trace_reduce.reduce(part, n_devices)
    with open(path, "w") as f:
        json.dump({"trace": part, "expect": {
            "busy_s": r["busy_s"], "idle_share": r["idle_share"],
            "top_ops": [n for n, _ in r["device_ops"][:3]]}}, f)


def serve_config(traffic: Dict):
    from repro.serve import ServeConfig
    s = dict(traffic["server"])
    s["batch_buckets"] = tuple(s["batch_buckets"])
    return ServeConfig(**s)


def compare(cfg: Dict, params, frames: np.ndarray, log: loadgen.Log,
            per_request: int, root: Path = ROOT) -> Dict:
    """Every answered request's output against the reference's on its own
    frames, whatever their shape (logits, an image): the worst frame's max
    |served - reference| over the reference output's max |.|."""
    answered = [i for i, o in enumerate(log.out) if o is not None]
    ref = reference.logits(cfg, params, frames,
                           chunk=cfg["check"]["ref_chunk"], root=root)
    ref = ref.reshape(-1, per_request, *ref.shape[1:])
    worst, shape_bad = 0.0, 0
    for i in answered:
        out = np.asarray(log.out[i])
        want = ref[log.payload[i]]
        if out.shape != want.shape or not np.all(np.isfinite(out)):
            shape_bad += 1
            continue
        worst = max(worst, check.logit_err(out.reshape(per_request, -1),
                                           want.reshape(per_request, -1)))
    return {"compared": len(answered) - shape_bad, "logit_err": worst,
            "malformed": shape_bad}


def run_cell(bench: Dict, cell: Dict, cfg: Dict, traffic: Dict, seed: int,
             seconds: float, trace: bool, options, devices, peak: Dict,
             hooks=None, t_start: float = T_START,
             keep_trace: Optional[str] = None, root: Path = ROOT) -> Dict:
    """One run of ``cell``; returns the result line without ``device``.
    ``root`` is the checkout whose ``bench/layers`` and ``bench/metrics``
    hold the configuration's layer kinds and the cell's readers."""
    from repro.serve import AdmissionError, Server
    per_request = traffic["frames_per_request"]
    n_payloads = traffic["distinct_frames"] // per_request
    compiles = CompileCounter()
    gcw = GcWatch()
    params = model.make_params(cfg, seed, root)
    frames = model.make_frames(cfg, n_payloads * per_request, seed)
    payloads = [frames[p * per_request:(p + 1) * per_request]
                for p in range(n_payloads)]
    order = np.random.default_rng([seed, 3]).permutation(n_payloads)

    program = model.make_program(cfg, params, root)
    server = Server(serve_config(traffic), hooks=hooks)
    hosted = server.register(cfg["name"], program, options,
                             buckets=traffic["server"]["batch_buckets"])
    server.start(warm=True)
    warm_shapes(hosted, traffic)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    watch = WindowWatch(hosted.metrics, trace_dir,
                        min(seconds, TRACE_SECONDS), devices)

    def submit(p: int):
        try:
            return server.submit(cfg["name"], payloads[p], block=False)
        except AdmissionError:
            return None

    try:
        if traffic["loop"] == "closed":
            log = loadgen.closed_loop(
                submit, n_payloads, order, traffic["outstanding"],
                traffic["warm_requests"], seconds, on_window=watch.start)
        else:
            gaps = loadgen.poisson_gaps(traffic["rate_rps"], seconds,
                                        traffic["schedule_seed"], seed)
            warm = loadgen.poisson_gaps(traffic["rate_rps"],
                                        traffic["warm_seconds"],
                                        traffic["schedule_seed"] + 1, seed)
            log = loadgen.open_loop(submit, n_payloads, order, warm, gaps,
                                    on_window=watch.start)
    finally:
        watch.join()
        server.stop(drain=True, timeout=120.0)
    say(f"{loadgen.now() - t_start:.1f} s since start: window closed, "
        f"answers drained, server stopped")
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    # every chip of the cell has to have served: the check below then
    # covers results from each of them
    frames_by_device = [d["frames"]
                        for d in server.stats()["pool"]["per_device"]]
    say(f"frames served by each device: {frames_by_device}")
    serving = sum(1 for f in frames_by_device if f > 0)
    del server, hosted, program

    win = log.in_window()
    lat = log.latencies_ms(win)
    # a refused request was answered (by the refusal); one that never
    # returned was not
    unanswered = int((np.isnan(np.asarray(log.done)[win])
                      & ~np.asarray(log.refused, dtype=bool)[win]).sum())
    failed = int(np.isinf(lat).sum())
    late_n, late_max = loadgen.lateness(log, win)
    in_window = compiles.between(log.t0, log.t1)
    say(f"window {log.t1 - log.t0:.3f} s: {len(win)} requests due, "
        f"{failed} refused, failed or unanswered; compiles and traces in "
        f"the window: {len(in_window)} {sorted(set(in_window))}")
    say(f"generator: {late_n} of {len(win)} requests sent more than 1 ms "
        f"late, the latest by {late_max:.3f} ms")
    say("latency percentiles (ms): " + ", ".join(
        f"p{q:g} {percentile_ms(lat, q)}" for q in (50, 90, 95, 99, 99.9)))
    say(gcw.summary(log.t0, log.t1))
    gcw.close()
    say(f"frames completed in each second of the window: "
        f"{log.completed_by_second(per_request)}")
    fps = log.completed_in_window(per_request) / seconds
    setup_s = log.t0 - t_start

    reduced = None
    traced = watch.part("trace0", "trace1")
    if trace:
        if traced is not None:
            say(f"traced part: {traced['seconds']:.3f} s, {traced['frames']} "
                f"frames in {traced['batches']} batches")
        try:
            if traced is None or traced["seconds"] < 0.5 * watch.trace_s:
                # the profiler's start took the rest of the window
                raise ValueError("the traced part is too short to read")
            tr = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
            reduced = trace_reduce.reduce(tr, len(devices))
            if keep_trace:
                keep(tr, len(devices), keep_trace)
        except (FileNotFoundError, ValueError) as e:
            say(f"trace not reduced: {e}")
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    got = compare(cfg, params, frames, log, per_request, root)
    say(f"{loadgen.now() - t_start:.1f} s since start: trace read, "
        f"answers compared")
    limit = cfg["check"]["logit_err_limit"]
    correct = (got["compared"] > 0 and got["malformed"] == 0
               and unanswered == 0 and serving >= len(devices)
               and limit is not None and got["logit_err"] <= limit)

    if not trace:
        values = {"frames_per_s": fps, "setup_s": setup_s,
                  "p50_ms": percentile_ms(lat, 50),
                  "p99_ms": percentile_ms(lat, 99)}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cells.end_to_end_metrics(bench, cell["name"])}
    else:
        bucket = max(traffic["server"]["batch_buckets"])
        host = watch.part("t0", "host1")
        if host is not None:
            say(f"host part: {host['seconds']:.3f} s, {host['frames']} "
                f"frames in {host['batches']} batches; then "
                f"{len(watch.spans)} program spans")
        ctx = {
            "config": cfg, "traffic": traffic, "chips": len(devices),
            "peak": peak, "seconds": seconds, "frames_per_s": fps,
            "ops_per_frame": work.ops_per_frame(cfg, root),
            "least_time_per_frame_s":
                work.least_time_s(cfg, bucket, peak, root) / bucket,
            # each with its "seconds": the untraced host part, the part
            # the profiler recorded, the whole window
            "parts": {"host": host, "spans": watch.part("host1", "spans1"),
                      "trace": traced, "window": watch.part("t0", "t1")},
            "spans": watch.spans,
            "trace": reduced,
        }
        metrics = {}
        for m in cells.per_layer_metrics(bench, cell["name"]):
            v = cells.metric_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(len(win)),
              "failed": failed, "metrics": metrics,
              "memory_peak_bytes": int(mem)}
    if reduced is not None:
        result["busy_s"] = reduced["busy_s"]
        result["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {
        "logit_err": {"value": got["logit_err"], "limit": limit},
        "unanswered": {"value": unanswered, "limit": 0},
        "malformed": {"value": got["malformed"], "limit": 0},
        "compared": {"value": got["compared"], "limit": ">0"},
        "devices_serving": {"value": serving, "limit": f">={len(devices)}"}}
    return result


def prepare(workload: str) -> Dict:
    """A cell's pieces, found by name, and the checks before a run: the
    compile cache, a TPU with the chips the cell asks for, its peaks, and
    options that resolve to the pallas backend compiled for the chip.
    Exits, printing no result, where one fails."""
    bench = cells.load_benchmark(ROOT)
    cell = cells.cell(bench, workload)
    cfg = cells.config(ROOT, bench, cell["config"])
    traffic = cells.traffic(ROOT, cell["traffic"])
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repro package is not in this checkout ({e})")
    cache = enable_compile_cache()
    import jax
    # cache every program, however quick to compile: set-up then does the
    # same work in every run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell["chips"]:
        fail(f"the cell needs {cell['chips']} chips, JAX sees "
             f"{len(devices)}")
    kind = devices[0].device_kind
    try:
        peak = cells.peaks(ROOT, kind)
    except KeyError as e:
        fail(str(e))
    from repro.core.program import Options
    options = Options(scheme=model.scheme(cfg)).resolve()
    if options.backend != "pallas" or options.interpret:
        fail(f"the options resolve to backend={options.backend} "
             f"interpret={options.interpret}; the chip path needs pallas "
             f"compiled for the chip (is REPRO_KERNEL_BACKEND or "
             f"REPRO_FORCE_INTERPRET set?)")
    return {"bench": bench, "cell": cell, "cfg": cfg, "traffic": traffic,
            "options": options, "devices": devices, "kind": kind,
            "peak": peak, "cache": cache}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="PATH",
                    help="also write the first 20 ms of the trace, reduced "
                         "and raw, to PATH (JSON, for the tests)")
    args = ap.parse_args(argv)

    c = prepare(args.workload)
    cell, devices, kind = c["cell"], c["devices"], c["kind"]
    say(f"{cell['name']}: {kind} x {len(devices)}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}; compile cache "
        f"{c['cache']}; {c['options'].describe()}")
    result = run_cell(c["bench"], cell, c["cfg"], c["traffic"], args.seed,
                      args.seconds, bool(args.trace), c["options"],
                      devices[:cell["chips"]], c["peak"],
                      keep_trace=args.keep_trace)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": result.pop("memory_peak_bytes")}
    for key in ("busy_s", "window_s"):
        if key in result:
            device[key] = result.pop(key)
    checks = result.pop("checks")
    line = {**result, "device": device, "checks": checks}
    for name, chk in checks.items():
        say(f"check {name}: {chk['value']} limit {chk['limit']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
