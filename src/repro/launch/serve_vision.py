"""Vision/imaging serving driver over the ``repro.serve`` runtime.

Three serving modes, one API (``repro.Program`` / ``Options`` /
``Executable`` hosted in a ``repro.serve.Server``):

    # CNN classification throughput (closed-loop saturation)
    PYTHONPATH=src python -m repro.launch.serve_vision \
        --model lenet --scheme mx43 --batch 8 --batches 50

    # fixed-function imaging (repro.imaging pipelines)
    PYTHONPATH=src python -m repro.launch.serve_vision \
        --pipeline edge_detect --batch 8 --batches 50

    # open-loop Poisson load (latency under offered load)
    PYTHONPATH=src python -m repro.launch.serve_vision \
        --model lenet --load 500 --requests 200 --deadline-ms 100

    # device pool: fan batches across 4 (virtual) devices
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.serve_vision \
        --model lenet --load 500 --requests 64 --devices 4

Each run compiles once (``Server.register`` -> ``Executable``), warms
every batch bucket, then streams *single-frame requests* through the
async micro-batching scheduler: requests are coalesced up to
``--batch`` / ``--max-wait-ms``, padded to the nearest compiled bucket,
executed with per-frame CRC calibration (results bit-identical to
per-request ``Executable.run``), and completed on a separate thread while
the next batch is being collected — the serving-runtime descendant of the
old double-buffered feeder (its ``--depth`` knob is now
``ServeConfig.max_inflight``).

The default mode reports sustained frames/s under full backlog next to
the power model's simulated device FPS and kFPS/W — and, for imaging
pipelines, the PSNR of the quantized device output against the float
reference. ``--load`` switches to the open-loop Poisson generator and
reports p50/p95/p99 latency, achieved rate, and sheds at the offered
load. The kernel backend and conv strategy stay serving flags
(``--backend``, ``--conv-strategy``) mapped through ``Options``, and the
run header prints the fully *resolved* options.

``--trace out.json`` records the whole run through ``repro.obs`` and
exports Chrome-trace JSON: every request's latency decomposes into
queue-wait -> batch-assembly -> device -> split spans on its own lane
(open the file in chrome://tracing or https://ui.perfetto.dev), and the
run ends with the verbose per-program stats table plus the plan-cache /
conv-dispatch footer. See docs/observability.md.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import serve
from repro.core.program import Options
from repro.core.quant import W4A4, W3A4, W2A4, MX_43, MX_42
from repro.kernels import dispatch
from repro.models.vision import MODEL_INPUT_HWC, vision_program

SCHEMES = {"w4a4": W4A4, "w3a4": W3A4, "w2a4": W2A4,
           "mx43": MX_43, "mx42": MX_42}


def _imaging_frames(batch: int, size: int, seed: int) -> np.ndarray:
    from repro.data.synthetic import synthetic_textures
    imgs, _ = synthetic_textures(batch, hw=size, seed=seed)
    return np.asarray(imgs, np.float32)


def main(argv=None):
    ap = argparse.ArgumentParser()
    # choices = the IRs the executable device path supports (alexnet's IR
    # is schedule-only; see models.vision.MODEL_INPUT_HWC)
    ap.add_argument("--model", default="lenet",
                    choices=sorted(MODEL_INPUT_HWC))
    ap.add_argument("--pipeline", default=None,
                    help="serve a repro.imaging pipeline instead of a CNN")
    ap.add_argument("--scheme", default="mx43", choices=sorted(SCHEMES))
    ap.add_argument("--batch", type=int, default=8,
                    help="scheduler max_batch (largest micro-batch)")
    ap.add_argument("--batches", type=int, default=50,
                    help="device batches worth of frames to stream "
                         "(total frames = batch * batches)")
    ap.add_argument("--size", type=int, default=64,
                    help="imaging frame height/width (pipeline mode)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="micro-batch collection window")
    ap.add_argument("--load", type=float, default=None,
                    help="open-loop Poisson mode: offered requests/s")
    ap.add_argument("--requests", type=int, default=64,
                    help="requests to offer in --load mode")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline (late requests are shed)")
    ap.add_argument("--backend", default=None,
                    choices=sorted(dispatch.BACKENDS),
                    help="kernel backend (default: REPRO_KERNEL_BACKEND / "
                         "auto: pallas on TPU, reference elsewhere)")
    ap.add_argument("--conv-strategy", default=None,
                    choices=sorted(dispatch.CONV_STRATEGIES),
                    help="conv execution strategy (default: "
                         "REPRO_CONV_STRATEGY / auto VMEM heuristic)")
    ap.add_argument("--devices", type=int, default=1,
                    help="device-pool width: one warmed executable per "
                         "local device, least-loaded placement + work "
                         "stealing (on CPU export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--placement", default="least_loaded",
                    choices=sorted(serve.PLACEMENTS),
                    help="pool placement policy (--devices > 1)")
    ap.add_argument("--shard-batch", action="store_true",
                    help="shard the batch axis over local devices "
                         "(no-op on 1 device)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record an obs trace of the whole run and export "
                         "Chrome-trace JSON (open in chrome://tracing or "
                         "Perfetto); also prints the verbose stats table")
    ap.add_argument("--admin-port", type=int, default=None,
                    help="serve the ops endpoint (/metrics /healthz /readyz "
                         "/statusz /tracez) on this port for the run's "
                         "lifetime; 0 binds an ephemeral port and prints it")
    ap.add_argument("--log", default=None, metavar="OUT.jsonl",
                    help="structured JSON-lines event log (serve lifecycle, "
                         "SLO breaches, worker failures, flight dumps)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.batch < 1 or args.batches < 1 or args.requests < 1:
        ap.error("--batch, --batches and --requests must be >= 1")
    if args.devices < 1:
        ap.error("--devices must be >= 1")
    if args.load is not None and args.load <= 0:
        ap.error("--load must be > 0 requests/s")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    trace = None
    if args.trace is not None:
        from repro import obs
        trace = obs.enable()

    options = Options(scheme=SCHEMES[args.scheme], fc_batch=args.batch,
                      backend=args.backend, conv_strategy=args.conv_strategy,
                      shard_batch=args.shard_batch)

    if args.pipeline is not None:
        from repro.imaging import PIPELINES
        if args.pipeline not in PIPELINES:
            ap.error(f"unknown pipeline {args.pipeline!r}; "
                     f"choose from {sorted(PIPELINES)}")
        prog = PIPELINES[args.pipeline].program(args.size, args.size, 3)
        pool = _imaging_frames(max(2 * args.batch, 8), args.size, args.seed)
        name = f"pipeline={prog.name}"
    else:
        prog = vision_program(args.model, key=jax.random.PRNGKey(args.seed))
        h, w, c = prog.input_hwc
        rng = np.random.default_rng(args.seed + 1)
        pool = rng.random((max(2 * args.batch, 8), h, w, c), np.float32)
        name = f"model={args.model}"

    server = serve.Server(serve.ServeConfig(
        max_batch=args.batch, max_wait_ms=args.max_wait_ms,
        max_queue=max(8 * args.batch, 64),
        default_deadline_ms=args.deadline_ms,
        devices=args.devices, placement=args.placement,
        admin_port=args.admin_port, log_path=args.log))
    t0 = time.perf_counter()
    hosted = server.register(prog.name, prog, options)
    t_compile = time.perf_counter() - t0
    server.start(warm=True)
    if server.admin is not None:
        print(f"[serve_vision] admin endpoint at {server.admin.url} "
              f"(/metrics /healthz /readyz /statusz /tracez)")

    r = hosted.executable.report
    print(f"[serve_vision] {name} max_batch={args.batch} "
          f"buckets={list(hosted.buckets)} wait={args.max_wait_ms}ms "
          f"devices={args.devices} compile={t_compile * 1e3:.1f}ms")
    print(f"[serve_vision] options: {options.describe()}")
    resolved = options.resolve()
    if jax.default_backend() == "tpu" and (resolved.backend != "pallas"
                                           or resolved.interpret):
        print(f"[serve_vision] WARNING: on a TPU but running "
              f"backend={resolved.backend} interpret={resolved.interpret} "
              f"(REPRO_KERNEL_BACKEND / REPRO_FORCE_INTERPRET / --backend)"
              f" — not the compiled kernel path")
    if r.conv_strategy:
        # annotate each conv with its fused-segment membership: a conv
        # inside a segment executes in that segment's single launch, not
        # under its per-conv strategy
        seg_of = {n: i for i, seg in enumerate(r.fused_segments)
                  for n in seg["names"]}
        strat = " ".join(
            f"{n}={v['kind']}" + (f"({v['n_strips']}x{v['strip_rows']}rows)"
                                  if v["kind"] == "strip" else "")
            + (f"[fused#{seg_of[n]}]" if n in seg_of else "")
            for n, v in r.conv_strategy.items())
        print(f"[serve_vision] conv strategy: {strat}")
        if r.fused_segments:
            segs = " ".join(
                f"#{i}:{'+'.join(seg['names'])}"
                f"(halo={seg['halo_rows']}rows,"
                f"vmem={seg['vmem_bytes'] >> 10}KB)"
                for i, seg in enumerate(r.fused_segments))
            print(f"[serve_vision] fused segments: {segs}")

    if args.load is not None:
        rep = serve.poisson_load(server, prog.name, pool, rate_rps=args.load,
                                 n_requests=args.requests, seed=args.seed,
                                 deadline_ms=args.deadline_ms)
        assert rep.submitted + rep.rejected == args.requests
        assert rep.served + rep.shed == rep.submitted, \
            f"unaccounted requests: {rep}"
        lat = rep.latency_ms
        print(f"[serve_vision] offered {rep.offered_rps:,.0f} req/s x "
              f"{args.requests}: served {rep.served} "
              f"(shed {rep.shed}, rejected {rep.rejected}) at "
              f"{rep.achieved_rps:,.0f} req/s")
        if lat.get("count"):
            print(f"[serve_vision] latency p50={lat['p50']:.2f}ms "
                  f"p95={lat['p95']:.2f}ms p99={lat['p99']:.2f}ms "
                  f"max={lat['max']:.2f}ms")
        fps = rep.achieved_fps
    else:
        rep = serve.saturate(server, prog.name, pool,
                             n_requests=args.batches * args.batch)
        fps = rep.achieved_fps
    stats = server.stats(verbose=args.trace is not None)
    snap = stats["programs"][prog.name]
    print(f"[serve_vision] measured {fps:,.0f} frames/s on "
          f"{jax.default_backend()} (avg_batch "
          f"{snap['avg_batch']:.1f}, padding waste "
          f"{snap['padding_waste']:.1%}) | device model: "
          f"{r.fps:,.0f} FPS, {r.avg_power_w:.2f} W, "
          f"{r.kfps_per_w:.1f} kFPS/W")
    if args.devices > 1:
        p = stats["pool"]
        occ = " ".join(f"d{d['device']}={d['occupancy']:.0%}"
                       for d in p["per_device"])
        print(f"[serve_vision] pool: {p['devices']} devices "
              f"[{p['placement']}] steals={p['steals']} occupancy {occ}")

    if args.pipeline is not None:
        from repro.imaging import apply_float, psnr
        frames = pool[:args.batch]
        out = hosted.executable.run_per_frame(frames)
        ref = apply_float(prog.layers, prog.params, frames)
        print(f"[serve_vision] quantized-vs-float PSNR "
              f"{float(psnr(ref, out)):.2f} dB (per-frame calibration)")
    server.stop()
    if trace is not None:
        from repro import obs
        obs.disable()
        trace.export(args.trace)
        summ = trace.summary()
        dev = summ.get("serve.request.device", {"count": 0, "total_ms": 0.0})
        print("[serve_vision] stats breakdown:")
        print(serve.format_stats(stats))
        print(f"[serve_vision] trace: {len(trace.records())} records "
              f"({dev['count']} device spans, {dev['total_ms']:.1f} ms "
              f"device time) -> {args.trace}")
    return fps


if __name__ == "__main__":
    main()
