#!/usr/bin/env python3
"""Chip smoke test: the serving path on one TPU, checked against the
reference backend on the same chip.

    python3 chip_smoke.py [--seed 0]      # one chip: the phases below
    python3 chip_smoke.py --four-chip     # four chips: pool + shard_batch

Phases (one chip), all through ``Program -> Executable -> Server`` with
``backend=pallas`` and ``interpret=False``:

  vgg16  VGG16 at 224x224x3 (138 M parameters, scheme mx43), served with
         ``max_batch`` 8 and warmed buckets, 16 single-frame requests.
  vgg9   the paper's Table-1 VGG9 with compressive acquisition (ca_pool).
  lenet  LeNet, whose two convs run as one fused-chain launch
         (``fused_kernel``).
  edge   denoise_gauss -> edge_detect at 256x256x3: the depthwise strip
         kernel, the ca_pool RGB->gray front and the imaging convs.

Each classifier phase runs the same frames through the same program
compiled with ``backend="reference"`` on the chip and requires: the
integer CRC codes entering the last layer equal, the logits within
``LOGIT_TOL`` of the reference, and the same top-1 on every frame. The
imaging phase requires a PSNR against the float reference
(``imaging.apply_float``) of at least ``EDGE_PSNR_FLOOR``. Any phase that
fails, or a conv traced for the reference backend while the pallas
backend is pinned (``dispatch.conv.reference``), fails the run.

``--four-chip`` runs only what exists across chips: VGG16 through
``Server(devices=4)`` against ``devices=1`` on the same requests (bitwise),
with work landing on every device, and ``Options(shard_batch=True)`` at
batch 8 against the unsharded run (bitwise).

Frames/s printed here is a smoke reading (16 requests, host clock), not a
benchmark. The script exits non-zero and prints no result line when JAX
finds no TPU or when the ``repro`` package is not next to it. Its last
line on success is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

# Logits come from identical integer accumulates and one dequant; the
# backends may evaluate that dequant (a product of three f32 values plus the
# bias) in another order, a few ulp (~1e-7 relative). One changed code moves
# a logit by one weight level times the scale, ~1e-2 of the logit range.
LOGIT_TOL = 1e-5          # max |logit - reference| / max |reference logit|

# tests/test_imaging.py PSNR_FLOORS["edge_detect"] (set at 32x32), kept
# unchanged at 256x256: the denoise -> edge chain there measures 22.79 dB
# under mx43 on CPU, on both backends (seed-0 textures).
EDGE_PSNR_FLOOR = 20.0

MAX_BATCH = 8
EDGE_HW = 256


def _fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _dispatch_counts() -> dict:
    from repro import obs
    snap = {}
    for name in ("dispatch.conv.resident", "dispatch.conv.strip",
                 "dispatch.conv.fused", "dispatch.conv.reference",
                 "dispatch.ca.pallas", "dispatch.ca.reference",
                 "dispatch.fused.fallback"):
        c = obs.REGISTRY.get(name)
        snap[name] = c.get() if c is not None else 0
    return snap


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] - before.get(k, 0)}


def _codes(deq):
    """Integer CRC codes from per-frame-calibrated dequantized outputs.

    Each frame's values are ``codes * scale`` with ``scale = max / 15``
    (the CRC requant puts the frame max at code 15), so the codes come
    back exactly by rounding."""
    import numpy as np
    flat = np.asarray(deq, np.float64).reshape(deq.shape[0], -1)
    top = flat.max(axis=1, keepdims=True)
    scale = np.where(top > 0, top / 15.0, 1.0)
    return np.rint(flat / scale).astype(np.int64)


def _serve(program, options, frames, devices: int = 1):
    """Serve ``frames`` one request each; -> (outputs, compile+warm
    seconds, serving seconds, stats).

    A pool places batches round-robin, so that every device gets work
    however fast the first one drains its queue."""
    import numpy as np
    from repro import serve
    server = serve.Server(serve.ServeConfig(
        max_batch=MAX_BATCH, devices=devices,
        placement="round_robin" if devices > 1 else "least_loaded"))
    t0 = time.perf_counter()
    server.register(program.name, program, options)
    server.start(warm=True)
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    futs = [server.submit(program.name, f) for f in frames]
    out = np.concatenate([np.asarray(f.result(timeout=600)) for f in futs])
    t_serve = time.perf_counter() - t0
    stats = server.stats()
    server.stop()
    return out, t_compile, t_serve, stats


def _report_plan(exe) -> None:
    r = exe.report
    strat = " ".join(
        f"{n}={v['kind']}" + (f"({v['n_strips']}x{v['strip_rows']})"
                              if v["kind"] == "strip" else "")
        for n, v in r.conv_strategy.items())
    _say(f"  conv strategies: {strat or '-'}")
    segs = " ".join("+".join(s["names"]) for s in r.fused_segments)
    _say(f"  fused segments: {segs or '-'}")


def _classifier_phase(name, program, options, frames) -> None:
    import numpy as np
    from repro.core.program import Program
    _say(f"phase {name}: {program.name} {program.input_hwc} "
         f"x {len(frames)} requests")
    before = _dispatch_counts()
    logits, t_compile, t_serve, _ = _serve(program, options, frames)
    # the codes entering the last layer: the program without its last
    # layer, same params, same backend pin
    head = Program(program.layers[:-1], program.params, program.input_hwc,
                   name=f"{program.name}-head")
    feats = head.compile(options).run_per_frame(frames)
    feats = np.asarray(feats)
    counts = _delta(_dispatch_counts(), before)
    exe = program.compile(options)
    _report_plan(exe)
    _say(f"  compile+warm {t_compile:.2f} s; dispatch counters {counts}")
    ref_opts = dataclasses.replace(options, backend="reference")
    t0 = time.perf_counter()
    ref_logits = np.asarray(program.compile(ref_opts).run_per_frame(frames))
    ref_feats = np.asarray(head.compile(ref_opts).run_per_frame(frames))
    _say(f"  reference compile+run {time.perf_counter() - t0:.2f} s")
    _say(f"  smoke reading (not a benchmark): {len(frames) / t_serve:.2f} "
         f"frames/s over {len(frames)} requests")
    codes, ref_codes = _codes(feats), _codes(ref_feats)
    mism = int(np.sum(codes != ref_codes))
    span = float(np.max(np.abs(ref_logits)))
    diff = float(np.max(np.abs(logits - ref_logits)))
    top1 = logits.argmax(-1) == ref_logits.argmax(-1)
    _say(f"  codes into last layer: {codes.size} compared, {mism} differ; "
         f"max |logit diff| {diff:.3e} (logit range {span:.3e}, "
         f"tol {LOGIT_TOL:g} x range); top-1 agrees on "
         f"{int(top1.sum())}/{len(top1)}")
    if counts.get("dispatch.conv.reference") or \
            counts.get("dispatch.ca.reference"):
        _fail(f"{name}: reference kernels traced under the pallas backend")
    if not np.all(np.isfinite(logits)) or logits.shape != ref_logits.shape:
        _fail(f"{name}: logits not finite or of shape {logits.shape}")
    if mism:
        _fail(f"{name}: {mism} integer codes differ from the reference")
    if diff > LOGIT_TOL * span:
        _fail(f"{name}: logits differ from the reference by {diff:.3e}")
    if not top1.all():
        _fail(f"{name}: top-1 differs from the reference")


def _edge_phase(options, seed: int) -> None:
    import numpy as np
    from repro.data.synthetic import synthetic_textures
    from repro.imaging import PIPELINES, apply_float, psnr
    hw = EDGE_HW
    program = PIPELINES["denoise_gauss"].program(hw, hw, 3).then(
        PIPELINES["edge_detect"].program(hw, hw, 3))
    frames, _ = synthetic_textures(4, hw=hw, seed=seed)
    frames = np.asarray(frames, np.float32)
    _say(f"phase edge: {program.name} {program.input_hwc} "
         f"x {len(frames)} requests")
    before = _dispatch_counts()
    out, t_compile, t_serve, _ = _serve(program, options, frames)
    counts = _delta(_dispatch_counts(), before)
    _report_plan(program.compile(options))
    _say(f"  compile+warm {t_compile:.2f} s; dispatch counters {counts}")
    ref = np.asarray(apply_float(program.layers, program.params, frames))
    p = float(psnr(ref, out))
    dev_ref = np.asarray(program.compile(dataclasses.replace(
        options, backend="reference")).run_per_frame(frames))
    mism = int(np.sum(_codes(out) != _codes(dev_ref)))
    _say(f"  smoke reading (not a benchmark): {len(frames) / t_serve:.2f} "
         f"frames/s over {len(frames)} requests")
    _say(f"  PSNR vs float reference {p:.2f} dB (floor {EDGE_PSNR_FLOOR}), "
         f"reference backend {float(psnr(ref, dev_ref)):.2f} dB; vs "
         f"reference backend: {mism}/{out.size} codes differ, max "
         f"|diff| {float(np.max(np.abs(out - dev_ref))):.3e}")
    if counts.get("dispatch.conv.reference") or \
            counts.get("dispatch.ca.reference"):
        _fail("edge: reference kernels traced under the pallas backend")
    if not np.all(np.isfinite(out)) or out.shape != ref.shape:
        _fail(f"edge: output not finite or of shape {out.shape}")
    if p < EDGE_PSNR_FLOOR:
        _fail(f"edge: PSNR {p:.2f} dB under the {EDGE_PSNR_FLOOR} dB floor")


def _four_chip(options, seed: int) -> None:
    import jax
    import numpy as np
    from repro.models.vision import vision_program
    if len(jax.devices()) != 4:
        _fail(f"--four-chip needs 4 devices, JAX sees {len(jax.devices())}")
    program = vision_program("vgg16", key=jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed + 1)
    frames = rng.random((32, *program.input_hwc), np.float32)
    one, _, t1, _ = _serve(program, options, frames, devices=1)
    four, t_compile, t4, stats = _serve(program, options, frames, devices=4)
    per_dev = [d["batches"] for d in stats["pool"]["per_device"]]
    _say(f"pool: devices=4 compile+warm {t_compile:.2f} s; batches per "
         f"device {per_dev}; smoke reading (not a benchmark) "
         f"{len(frames) / t4:.2f} frames/s vs {len(frames) / t1:.2f} on "
         f"one device; bitwise equal: {np.array_equal(one, four)}")
    if not np.array_equal(one, four):
        _fail("pool: devices=4 results differ from devices=1")
    if min(per_dev) < 1:
        _fail(f"pool: some device ran no batch ({per_dev})")
    batch = frames[:MAX_BATCH]
    plain = np.asarray(program.compile(options).run_per_frame(batch))
    sharded_exe = program.compile(dataclasses.replace(options,
                                                      shard_batch=True))
    t0 = time.perf_counter()
    sharded = sharded_exe.run_per_frame(batch)
    sharded.block_until_ready()
    n_shards = len(sharded.sharding.device_set)
    sharded = np.asarray(sharded)
    _say(f"shard_batch: batch {MAX_BATCH} over {n_shards} devices "
         f"(compile+run {time.perf_counter() - t0:.2f} s); bitwise equal "
         f"to unsharded: {np.array_equal(plain, sharded)}")
    if n_shards != 4:
        _fail(f"shard_batch: result spans {n_shards} devices, not 4")
    if not np.array_equal(plain, sharded):
        _fail("shard_batch: sharded results differ from unsharded")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the random weights and frames")
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip pool and shard_batch "
                         "comparisons")
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        _fail(f"the repro package is not next to this script ({e})")
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        _fail(f"JAX found no TPU (platform {dev.platform!r})")
    from repro.core.program import Options
    from repro.core.quant import MX_43
    # what the environment resolves to, pinned for every phase
    options = Options(scheme=MX_43).resolve()
    _say(f"device {dev.device_kind} x {len(devices)}; compile cache {cache}")
    _say(f"resolved options: {options.describe()}")
    if options.backend != "pallas" or options.interpret:
        _fail(f"the options resolve to backend={options.backend} "
              f"interpret={options.interpret}; the chip path needs "
              f"pallas, interpret=False (is REPRO_KERNEL_BACKEND or "
              f"REPRO_FORCE_INTERPRET set?)")
    if args.four_chip:
        _four_chip(options, args.seed)
    else:
        import numpy as np
        from repro.models.vision import vision_program
        rng = np.random.default_rng(args.seed + 1)
        for name, n in (("vgg16", 16), ("vgg9", 8), ("lenet", 8)):
            program = vision_program(name, key=jax.random.PRNGKey(args.seed))
            frames = rng.random((n, *program.input_hwc), np.float32)
            _classifier_phase(name, program, options, frames)
        _edge_phase(options, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
