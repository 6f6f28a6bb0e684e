"""Serving-runtime benchmark: latency/throughput under offered load.

Three measurements into ``BENCH_serving.json`` (all on the deterministic
``reference`` backend so the numbers are comparable across machines):

* **capacity** — the service ceiling: closed-loop saturation (every
  submit under backpressure, server permanently backlogged) through the
  full micro-batching scheduler, per program.
* **offered-load sweep** — open-loop Poisson arrivals at fractions of
  that capacity; per point: p50/p95/p99 client-side latency, achieved
  request rate, sheds/rejections, padding waste. The latency curve's
  knee as offered load crosses capacity is the serving story.
* **batch-bucket ablation** — the acceptance gate: the same saturating
  workload served request-at-a-time (``max_batch=1``, buckets ``(1,)``)
  vs micro-batched; micro-batching must sustain >= 2x the frames/s.
* **device-pool ablation** (schema v2) — the same saturating workload
  through a ``devices=1`` vs ``devices=4`` server, in this process, on
  its own local devices: four chips of one host, or on CPU four virtual
  devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4``, set
  before JAX starts — ``benchmarks/run.py`` does so). No child process is
  started: a process that has touched JAX holds the chip. With fewer than
  four local devices the run fails. The **gated** number uses an
  *emulated* device: the ``Hooks.execute`` seam replaces the XLA call
  with a GIL-releasing sleep proportional to the padded bucket, so the
  measurement isolates the host runtime's ability to keep N devices
  fed — which is the thing the pool exists to prove, and the honest
  analogue of the paper's optical device computing off-host. (Real-XLA
  virtual devices share this machine's CPU cores, so their scaling is
  reported alongside but not gated.)

Run: ``XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src
python -m benchmarks.bench_serving [--quick]``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import jax
import numpy as np

import repro
from repro import serve
from repro.core.quant import W4A4

SCHEMA_VERSION = 2
OUT_PATH = Path(__file__).resolve().parent / "BENCH_serving.json"
LOAD_FRACTIONS = (0.25, 0.5, 1.0, 1.5)
PROGRAMS = ("lenet", "edge_detect")
POOL_DEVICES = 4
POOL_PER_FRAME_MS = 2.0   # emulated device service time per batch slot


def _program(name: str) -> repro.Program:
    if name == "lenet":
        return repro.Program.from_model("lenet",
                                        key=jax.random.PRNGKey(0))
    return repro.Program.from_pipeline(name, 32, 32, 3)


def _pool(prog: repro.Program, n: int = 32, seed: int = 0) -> np.ndarray:
    h, w, c = prog.input_hwc
    rng = np.random.default_rng(seed)
    return rng.random((n, h, w, c)).astype(np.float32)


def _server(progs, max_batch: int, buckets=None,
            max_wait_ms: float = 2.0) -> serve.Server:
    srv = serve.Server(serve.ServeConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        max_queue=max(16 * max_batch, 128)))
    options = repro.Options(scheme=W4A4, backend="reference")
    for name, prog in progs.items():
        srv.register(name, prog, options, buckets=buckets)
    return srv.start(warm=True)


def _pool_ablation(quick: bool = False) -> dict:
    """devices=1 vs devices=4 capacity, emulated and real XLA, on this
    process's own local devices (at least ``POOL_DEVICES`` of them)."""
    n_requests = 96 if quick else 240
    if len(jax.local_devices()) < POOL_DEVICES:
        raise RuntimeError(
            f"the device-pool ablation needs {POOL_DEVICES} local devices, "
            f"JAX sees {len(jax.local_devices())}: run on a "
            f"{POOL_DEVICES}-chip host, or on CPU with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={POOL_DEVICES}")
    prog = _program("lenet")
    frames = _pool(prog)
    options = repro.Options(scheme=W4A4, backend="reference")
    per_frame_s = POOL_PER_FRAME_MS / 1e3

    def emulated(program, device, frames_, bucket, default):
        # stand-in device: sleeps (GIL-free) for the padded batch's
        # service time, so N workers genuinely overlap — measures the
        # host runtime, not this machine's core count
        time.sleep(per_frame_s * bucket)
        return np.zeros((frames_.shape[0], 8), np.float32)

    def capacity(ndev, hooks=None, warm=False):
        srv = serve.Server(serve.ServeConfig(
            max_batch=4, max_wait_ms=1.0, max_queue=128, devices=ndev),
            hooks=hooks)
        srv.register("lenet", prog, options)
        srv.start(warm=warm)
        fps = serve.saturate(srv, "lenet", frames,
                             n_requests=n_requests).achieved_fps
        pool_stats = srv.stats()["pool"]
        srv.stop()
        return fps, pool_stats

    hooks = serve.Hooks(execute=emulated)
    em1, _ = capacity(1, hooks)
    em4, st4 = capacity(POOL_DEVICES, hooks)
    x1, _ = capacity(1, warm=True)
    x4, _ = capacity(POOL_DEVICES, warm=True)
    return {
        "devices": POOL_DEVICES,
        "n_requests": n_requests,
        "per_frame_ms": POOL_PER_FRAME_MS,
        "emulated": {"pool1_fps": em1, "pool4_fps": em4,
                     "speedup": em4 / max(em1, 1e-9),
                     "steals": st4["steals"]},
        "xla": {"pool1_fps": x1, "pool4_fps": x4,
                "speedup": x4 / max(x1, 1e-9),
                "host_cores": os.cpu_count()},
    }


def run(csv: bool = True, quick: bool = False,
        max_batch: int = 16, n_requests: int = 300):
    if quick:
        n_requests = 80
    progs = {name: _program(name) for name in PROGRAMS}
    pools = {name: _pool(prog) for name, prog in progs.items()}
    out_lines = []

    # -- capacity: closed-loop saturation through the micro-batcher --------
    capacity = {}
    for name in PROGRAMS:
        srv = _server({name: progs[name]}, max_batch)
        # best of two: the first saturation still pays residual process
        # warm-up (allocator growth, first host->device copies), which
        # would understate the capacity the sweep loads are scaled from
        fps = max(
            serve.saturate(srv, name, pools[name],
                           n_requests=n_requests).achieved_fps
            for _ in range(2))
        srv.stop()
        capacity[name] = fps
        out_lines.append(
            f"bench_serving.capacity.{name},{1e6 / fps:.0f},fps={fps:.0f}")

    # -- offered-load sweep (Poisson, open loop) on the primary program ----
    primary = PROGRAMS[0]
    sweep = []
    for frac in LOAD_FRACTIONS:
        rate = frac * capacity[primary]
        srv = _server({primary: progs[primary]}, max_batch)
        rep = serve.poisson_load(srv, primary, pools[primary],
                                 rate_rps=rate, n_requests=n_requests,
                                 seed=7)
        snap = srv.stats()["programs"][primary]
        srv.stop()
        point = dataclasses.asdict(rep)
        point["load_fraction"] = frac
        point["padding_waste"] = snap["padding_waste"]
        point["avg_batch"] = snap["avg_batch"]
        sweep.append(point)
        lat = rep.latency_ms
        out_lines.append(
            f"bench_serving.sweep.{primary}.x{frac:g},"
            f"{lat.get('p50', 0) * 1e3:.0f},"
            f"offered={rate:.0f}rps;achieved={rep.achieved_rps:.0f}rps;"
            f"p50={lat.get('p50', 0):.2f}ms;p95={lat.get('p95', 0):.2f}ms;"
            f"p99={lat.get('p99', 0):.2f}ms;shed={rep.shed};"
            f"rejected={rep.rejected};avg_batch={snap['avg_batch']:.1f}")

    # -- ablation: request-at-a-time vs micro-batched at saturation --------
    srv1 = _server({primary: progs[primary]}, max_batch=1, buckets=(1,))
    rep1 = serve.saturate(srv1, primary, pools[primary],
                          n_requests=n_requests)
    srv1.stop()
    srvN = _server({primary: progs[primary]}, max_batch)
    repN = serve.saturate(srvN, primary, pools[primary],
                          n_requests=n_requests)
    snapN = srvN.stats()["programs"][primary]
    srvN.stop()
    speedup = repN.achieved_fps / max(rep1.achieved_fps, 1e-9)
    ablation = {
        "program": primary,
        "batch1_fps": rep1.achieved_fps,
        "microbatch_fps": repN.achieved_fps,
        "max_batch": max_batch,
        "avg_batch": snapN["avg_batch"],
        "speedup": speedup,
    }
    out_lines.append(
        f"bench_serving.ablation.{primary},"
        f"{1e6 / repN.achieved_fps:.0f},"
        f"batch1_fps={rep1.achieved_fps:.0f};"
        f"microbatch_fps={repN.achieved_fps:.0f};speedup={speedup:.2f}x")

    # -- device-pool ablation (4 local devices, this process) --------------
    pool_abl = _pool_ablation(quick)
    em = pool_abl["emulated"]
    out_lines.append(
        f"bench_serving.pool_ablation.emulated,"
        f"{1e6 / max(em['pool4_fps'], 1e-9):.0f},"
        f"pool1_fps={em['pool1_fps']:.0f};"
        f"pool4_fps={em['pool4_fps']:.0f};"
        f"speedup={em['speedup']:.2f}x;steals={em['steals']}")
    xl = pool_abl["xla"]
    out_lines.append(
        f"bench_serving.pool_ablation.xla,"
        f"{1e6 / max(xl['pool4_fps'], 1e-9):.0f},"
        f"pool1_fps={xl['pool1_fps']:.0f};"
        f"pool4_fps={xl['pool4_fps']:.0f};"
        f"speedup={xl['speedup']:.2f}x;"
        f"host_cores={xl['host_cores']} (ungated)")

    payload = {
        "schema_version": SCHEMA_VERSION,
        "backend": "reference",
        "host": jax.default_backend(),
        "max_batch": max_batch,
        "n_requests": n_requests,
        "capacity_fps": capacity,
        "sweep": sweep,
        "ablation": ablation,
        "pool_ablation": pool_abl,
    }
    OUT_PATH.write_text(json.dumps(payload, indent=2) + "\n")
    if csv:
        print("\n".join(out_lines))
        print(f"bench_serving.json,0.0,path={OUT_PATH}")
    return payload


if __name__ == "__main__":
    run(quick="--quick" in sys.argv)
