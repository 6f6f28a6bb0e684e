"""Profiler trace -> device busy time, idle share, op times and idle gaps.

The benchmark records the device planes only: with the host tracer on,
serving slows three-fold because transfers stop overlapping the device's
work. So the host clock does not appear in the trace, and the traced
window is marked on each device instead: the benchmark runs a tiny
program named ``WINDOW_MARK`` on every device it uses as the window opens
and again as it closes. A device's window runs from the end of its
opening mark to the start of its closing mark, on the trace's clock.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
structure, which the reductions below work on and which a test can keep as
JSON::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]},
                           {"name": "marks", "events": [...]}]}]}

- Busy time of a device: the union of the intervals of its ``XLA Ops``
  events inside its window. Idle share: 1 - busy / window, each averaged
  over the devices reduced.
- Op times: the summed durations of the device ops, by name, over the
  devices reduced.
- Idle gaps: the longest stretches inside a window in which a device ran
  no op, each named by the op that ran last before it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MARK_LINE = "marks"
WINDOW_MARK = "bench_trace_window"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def short_name(name: str) -> str:
    """A device op's name as the trace shows it, without the HLO text that
    follows it: ``%conv_strip_kernel.11 = f32[...] custom-call(...)`` ->
    ``conv_strip_kernel.11``."""
    return name.split(" = ", 1)[0].lstrip("%") if name.startswith("%") \
        else name


def load(path: str) -> Dict:
    """Each device plane's ops and window marks: what the reductions read,
    and no more (a trace holds other lines of many events). The marks are
    the events of any line whose name holds ``WINDOW_MARK``: the mark
    program's module (``jit_bench_trace_window(...)``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name) is None:
            continue
        ops, marks = [], []
        for line in plane.lines:
            for e in line.events:
                if WINDOW_MARK in e.name:
                    marks.append([WINDOW_MARK, int(e.start_ns),
                                  int(e.duration_ns)])
                elif line.name == OP_LINE:
                    ops.append([short_name(e.name), int(e.start_ns),
                                int(e.duration_ns)])
        planes.append({"name": plane.name, "lines": [
            {"name": OP_LINE, "events": ops},
            {"name": MARK_LINE, "events": marks}]})
    return {"planes": planes}


def device_planes(tr: Dict, n: Optional[int] = None) -> List[Dict]:
    """The TPU planes in device order; the first ``n`` if given."""
    planes = [p for p in tr["planes"] if DEVICE_PLANE.match(p["name"])]
    planes.sort(key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))
    return planes[:n] if n is not None else planes


def _line(plane: Dict, name: str) -> List[list]:
    return [e for line in plane["lines"] if line["name"] == name
            for e in line["events"]]


def op_events(plane: Dict) -> List[list]:
    return _line(plane, OP_LINE)


def window(plane: Dict) -> Tuple[int, int]:
    """A device's traced window: from the end of its opening marks to the
    start of its closing marks (the marks fall in two groups, split at the
    middle of their span)."""
    marks = _line(plane, MARK_LINE)
    if len(marks) < 2:
        raise ValueError(f"{plane['name']} has {len(marks)} "
                         f"{WINDOW_MARK!r} marks, not an opening and a "
                         f"closing one")
    first = min(s for _, s, _ in marks)
    last = max(s for _, s, _ in marks)
    mid = (first + last) / 2
    lo = max(s + d for _, s, d in marks if s <= mid)
    hi = min(s for _, s, _ in marks if s > mid)
    if hi <= lo:
        raise ValueError(f"{plane['name']}: the window marks overlap")
    return lo, hi


def cut(tr: Dict, seconds: float) -> Dict:
    """The first ``seconds`` of each device's window, as a trace of its
    own: the ops that start inside it, and marks at its two ends."""
    planes = []
    for p in device_planes(tr):
        lo, hi = window(p)
        hi = min(hi, lo + int(seconds * 1e9))
        planes.append({"name": p["name"], "lines": [
            {"name": OP_LINE, "events": [e for e in op_events(p)
                                         if lo <= e[1] < hi]},
            {"name": MARK_LINE, "events": [[WINDOW_MARK, lo - 1, 1],
                                           [WINDOW_MARK, hi, 1]]}]})
    return {"planes": planes}


def union(intervals: List[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals, clipped to [lo, hi)."""
    merged: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: List[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def reduce(tr: Dict, n_devices: int, top: int = 10) -> Dict:
    """Busy and idle over each device's window, averaged over the first
    ``n_devices`` device planes, with the top ops and idle gaps."""
    planes = device_planes(tr, n_devices)
    if not planes:
        raise ValueError("no TPU device plane in the trace")
    busy_each, window_each, op_ns, named_gaps = [], [], {}, []
    for plane in planes:
        lo, hi = window(plane)
        evs = op_events(plane)
        busy = union([(s, s + d) for _, s, d in evs], lo, hi)
        busy_each.append(sum(e - s for s, e in busy) / 1e9)
        window_each.append((hi - lo) / 1e9)
        for name, s, d in evs:
            d = min(s + d, hi) - max(s, lo)
            if d > 0:
                op_ns[name] = op_ns.get(name, 0) + d
        ends = sorted((s + d, name) for name, s, d in evs if lo < s + d <= hi)
        keys = [t for t, _ in ends]
        for g0, g1 in gaps(busy, lo, hi):
            i = bisect.bisect_right(keys, g0) - 1
            name = f"after {ends[i][1]}" if i >= 0 else "window start"
            named_gaps.append([name, (g1 - g0) / 1e9])
    named_gaps.sort(key=lambda g: g[1], reverse=True)
    window_s = sum(window_each) / len(window_each)
    busy_s = sum(busy_each) / len(busy_each)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "busy_s_per_device": busy_each,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": [[n, t / 1e9] for n, t in sorted(
            op_ns.items(), key=lambda kv: kv[1], reverse=True)[:top]],
        "idle_gaps": named_gaps[:top],
    }
