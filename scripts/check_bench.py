"""Benchmark regression gate (run by scripts/ci.sh).

Two layers of checking over the committed ``benchmarks/BENCH_*.json``
artifacts, so a PR that regenerates them cannot silently regress the
numbers they exist to pin:

  1. **Invariants** — absolute properties of the *current* files that must
     hold regardless of machine speed: the megakernel fusion ablation is
     bitwise-exact and at least ``FUSED_MIN_SPEEDUP``x faster than the
     per-conv path at 256x256; kernel-vs-oracle errors stay at float
     epsilon; the depthwise raw accumulate is exactly 0 error; serving
     micro-batching sustains ``SERVE_MIN_SPEEDUP``x request-at-a-time;
     the 4-virtual-device pool scales >= ``POOL_MIN_SCALING``x over one
     device on the emulated-device axis (serving schema >= 2);
     disabled-path obs overhead stays under ``OBS_MAX_OVERHEAD_PCT``;
     the always-on flight recorder costs < ``FLIGHT_MAX_OVERHEAD_PCT``
     of serving throughput (obs schema >= 2).
     Every numeric leaf in every file must additionally be *finite* — a
     NaN or inf scalar is always an artifact bug (empty-reservoir
     percentile, zero-window rate), never a measurement.
  2. **Regression band** — every timing (``*_us``) and throughput
     (``fps*``) scalar is compared against the same file at a baseline git
     ref (default ``HEAD``, override with ``--base``). Timings may not be
     more than ``tolerance``x slower and throughputs not more than
     ``tolerance``x lower (default 2.0 — CPU CI timing is noisy; override
     with ``--tolerance`` or ``REPRO_BENCH_TOLERANCE``). Improvements are
     never flagged.

Both layers are **schema-version-aware**: when ``schema_version`` differs
between the working tree and the baseline (a schema migration PR, like the
one that introduced ``fused_chain``), the regression band is skipped for
that file — there is nothing comparable to diff against — but the
invariants still run. A file missing at the baseline ref is treated the
same way.

Exit code 0 on success; prints every violation otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH_DIR = ROOT / "benchmarks"
FILES = ("BENCH_kernels.json", "BENCH_imaging.json", "BENCH_serving.json",
         "BENCH_obs.json", "BENCH_analysis.json")
FUSED_MIN_SPEEDUP = 1.5   # acceptance bar for the 256x256 chain ablation
SERVE_MIN_SPEEDUP = 2.0   # micro-batching vs request-at-a-time at saturation
POOL_MIN_SCALING = 1.5    # 4-device pool vs 1 device, emulated device time
ORACLE_ERR_MAX = 1e-5     # dequant float epsilon, not a kernel bug
OBS_MAX_OVERHEAD_PCT = 2.0  # disabled-path obs cost on the 3-stage chain
FLIGHT_MAX_OVERHEAD_PCT = 5.0  # always-on flight recorder, serving fps axis
VERIFY_MAX_OVERHEAD_PCT = 5.0  # plan verification riding the compile pass


def _baseline(name: str, ref: str):
    """The committed version of benchmarks/<name> at ``ref`` (None if new)."""
    proc = subprocess.run(
        ["git", "show", f"{ref}:benchmarks/{name}"],
        cwd=ROOT, capture_output=True, text=True)
    if proc.returncode != 0:
        return None
    return json.loads(proc.stdout)


def _scalars(obj, prefix=""):
    """Flatten to {dotted.path: float} for every numeric leaf."""
    flat = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            flat.update(_scalars(v, f"{prefix}{k}."))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            flat.update(_scalars(v, f"{prefix}{i}."))
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        flat[prefix[:-1]] = float(obj)
    return flat


def check_finite(name: str, data: dict, errors: list) -> None:
    """No NaN/inf scalar anywhere in a BENCH file (every file).

    A NaN percentile (e.g. from an empty latency reservoir) or an inf
    speedup (zero-window rate) is always an artifact bug, never a real
    measurement — and it silently poisons the regression band.
    """
    import math
    for path, v in _scalars(data).items():
        if not math.isfinite(v):
            errors.append(f"{name}: {path} is {v} — non-finite scalar")


def check_invariants(name: str, data: dict, errors: list) -> None:
    def bad(msg):
        errors.append(f"{name}: {msg}")

    if name == "BENCH_kernels.json":
        fused = data.get("fused_chain", {})
        if not fused:
            bad("fused_chain section missing (schema_version >= 2)")
        for hw, e in fused.items():
            if not e.get("bitwise_equal"):
                bad(f"fused_chain.{hw}: fused output not bitwise-identical")
            if e.get("speedup", 0.0) < FUSED_MIN_SPEEDUP:
                bad(f"fused_chain.{hw}: speedup {e.get('speedup'):.2f}x "
                    f"< required {FUSED_MIN_SPEEDUP}x")
        for sec in ("micro", "conv_strategy_sweep"):
            for key, e in data.get(sec, {}).items():
                for k, v in e.items():
                    if k.endswith("max_abs_err") and v > ORACLE_ERR_MAX:
                        bad(f"{sec}.{key}.{k}: {v:.2e} > {ORACLE_ERR_MAX}")
        dw = {k: v for k, v in data.get("conv_strategy_sweep", {}).items()
              if k.startswith("depthwise_")}
        for key, e in dw.items():
            if e.get("max_abs_err", 1.0) != 0.0:
                bad(f"conv_strategy_sweep.{key}: raw accumulate err "
                    f"{e['max_abs_err']} != 0")

    elif name == "BENCH_imaging.json":
        for pipe, e in data.get("pipelines", {}).items():
            for sname, s in e.get("schemes", {}).items():
                if s.get("fps", 0.0) <= 0:
                    bad(f"{pipe}.{sname}: non-positive fps")
            abl = e.get("fused_ablation")
            if abl is not None:
                if abl.get("fps_fused", 0.0) <= 0 \
                        or abl.get("fps_unfused", 0.0) <= 0:
                    bad(f"{pipe}.fused_ablation: non-positive fps")
                if not abl.get("segments"):
                    bad(f"{pipe}.fused_ablation: empty segment list")

    elif name == "BENCH_serving.json":
        abl = data.get("ablation", {})
        if abl.get("speedup", 0.0) < SERVE_MIN_SPEEDUP:
            bad(f"ablation: micro-batching speedup {abl.get('speedup')} "
                f"< required {SERVE_MIN_SPEEDUP}x")
        if data.get("schema_version", 1) >= 2:
            pool = data.get("pool_ablation")
            if not pool:
                bad("pool_ablation section missing (schema_version >= 2)")
            else:
                # the gated axis is the EMULATED-device scaling: it
                # measures the host runtime feeding 4 devices, which must
                # scale even on a 1-core CI box (the sleeps overlap).
                # xla.speedup is reported but not gated — real virtual
                # devices share the host's cores.
                em = pool.get("emulated", {})
                if em.get("speedup", 0.0) < POOL_MIN_SCALING:
                    bad(f"pool_ablation.emulated: 4-device scaling "
                        f"{em.get('speedup', 0.0):.2f}x < required "
                        f"{POOL_MIN_SCALING}x")

    elif name == "BENCH_obs.json":
        chain = data.get("chain", {})
        if "overhead_disabled_pct" not in chain:
            bad("chain.overhead_disabled_pct missing")
        elif chain["overhead_disabled_pct"] >= OBS_MAX_OVERHEAD_PCT:
            bad(f"chain.overhead_disabled_pct "
                f"{chain['overhead_disabled_pct']:.2f}% >= "
                f"{OBS_MAX_OVERHEAD_PCT}% — disabled tracing must be free")
        if chain.get("frame_us_raw", 0.0) <= 0:
            bad("chain.frame_us_raw must be > 0")
        if data.get("schema_version", 1) >= 2:
            fl = data.get("flight")
            if not fl:
                bad("flight section missing (schema_version >= 2)")
            else:
                if fl.get("fps_flight_on", 0.0) <= 0:
                    bad("flight.fps_flight_on must be > 0")
                if "overhead_pct" not in fl:
                    bad("flight.overhead_pct missing")
                elif fl["overhead_pct"] >= FLIGHT_MAX_OVERHEAD_PCT:
                    bad(f"flight.overhead_pct {fl['overhead_pct']:.2f}% >= "
                        f"{FLIGHT_MAX_OVERHEAD_PCT}% — the flight recorder "
                        f"is always on, it must stay near-free")

    elif name == "BENCH_analysis.json":
        v = data.get("verify", {})
        if "overhead_pct" not in v:
            bad("verify.overhead_pct missing")
        elif v["overhead_pct"] >= VERIFY_MAX_OVERHEAD_PCT:
            bad(f"verify.overhead_pct {v['overhead_pct']:.2f}% >= "
                f"{VERIFY_MAX_OVERHEAD_PCT}% — Options(verify=\"auto\") "
                f"rides every first compile, it must be ~free")
        if v.get("verify_us", 0.0) <= 0:
            bad("verify.verify_us must be > 0")
        lint = data.get("lint", {})
        if lint.get("errors", 1) != 0:
            bad(f"lint.errors = {lint.get('errors')} — the serve/obs tree "
                f"must be lint-clean when the artifact is regenerated")


def check_regression(name: str, data: dict, base: dict, tolerance: float,
                     errors: list, notes: list) -> None:
    if base is None:
        notes.append(f"{name}: no baseline at ref — regression band skipped")
        return
    if base.get("schema_version") != data.get("schema_version"):
        notes.append(
            f"{name}: schema_version {base.get('schema_version')} -> "
            f"{data.get('schema_version')} — regression band skipped")
        return
    cur, old = _scalars(data), _scalars(base)
    for path in sorted(set(cur) & set(old)):
        leaf = path.rsplit(".", 1)[-1]
        a, b = old[path], cur[path]
        if a <= 0 or b <= 0:
            continue
        if leaf.endswith("_us") and b / a > tolerance:
            errors.append(f"{name}: {path} slowed {b / a:.2f}x "
                          f"({a:.0f}us -> {b:.0f}us, tolerance "
                          f"{tolerance}x)")
        elif "fps" in path and a / b > tolerance:
            errors.append(f"{name}: {path} throughput dropped "
                          f"{a / b:.2f}x ({a:.0f} -> {b:.0f} fps, "
                          f"tolerance {tolerance}x)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--base", default="HEAD",
                    help="git ref to diff the JSONs against (default HEAD)")
    ap.add_argument("--tolerance", type=float,
                    default=float(os.environ.get("REPRO_BENCH_TOLERANCE",
                                                 "2.0")),
                    help="allowed slowdown factor before failing")
    args = ap.parse_args(argv)

    errors, notes = [], []
    for name in FILES:
        path = BENCH_DIR / name
        if not path.exists():
            errors.append(f"{name}: missing from benchmarks/")
            continue
        data = json.loads(path.read_text())
        check_finite(name, data, errors)
        check_invariants(name, data, errors)
        check_regression(name, data, _baseline(name, args.base),
                         args.tolerance, errors, notes)

    for n in notes:
        print(f"check_bench: note — {n}")
    if errors:
        for e in errors:
            print(f"check_bench: FAIL — {e}", file=sys.stderr)
        return 1
    print(f"check_bench: OK ({len(FILES)} files, "
          f"tolerance {args.tolerance}x vs {args.base})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
