"""Always-on flight recorder: per-thread rings of fixed-width integer records.

The ``Trace`` collector is export-on-demand: a timeline exists only if the
operator installed a collector *before* the anomaly. Production incidents
do not announce themselves, so this module keeps the last N records per
thread in a preallocated ring that records **even when tracing is off**;
:meth:`FlightRecorder.dump` reconstructs the final seconds before any
trigger (SLO breach, ``WorkerError``, stop-timeout stranding) as the same
Chrome-trace JSON ``scripts/check_trace.py --flight`` validates.

A record is ``WIDTH`` signed 64-bit integers in one flat buffer per ring::

    seq, ph, name, t0_ns, t1_ns, lane_tid, lane, trace_id, n_attrs,
    (key, value) x MAX_ATTRS

* ``ph`` is 0 for a span ("X"), 1 for an instant ("i");
* ``name``, ``lane`` and ``trace_id`` are string codes from the
  recorder's intern table (0: none). A string that ends in ``-<digits>``
  (``vgg9/req-41``) is coded as its interned prefix and the number, so
  per-request ids do not grow the table;
* ``lane_tid`` is the synthetic display lane of a retrospective span
  (0: the recording thread's own lane);
* each attribute is a key, ``index << 3 | tag`` of the interned key name,
  and a value: an int, a string code, a float's bits, a bool, None, or
  the code of a JSON text for anything else (lists). Up to ``MAX_ATTRS``
  are kept; a dump marks a record that had more.

Recording stores integers into the ring under a per-ring lock: it keeps
no reference to the caller's objects and creates no object the garbage
collector tracks, so the always-on recorder neither grows the heap nor
brings on collections. The ring overwrites its oldest record; the
per-ring ``seq`` stamps every record, so a dump (``check_trace.py
--flight``) or a reader of :meth:`FlightRecorder.rows` can prove the
history it holds has no gap.

Installation is process-global (``install()`` / ``uninstall()``), and
``repro.obs`` installs a default recorder at import time unless
``REPRO_FLIGHT=off`` (capacity via ``REPRO_FLIGHT_SLOTS``, default 2048
records per thread). ``obs.span``/``obs.event``/``obs.span_at``/
``obs.span_ns`` feed the recorder from ``trace.py`` whenever one is
installed, independent of the ``Options(trace=)`` tri-state (``span_ns``,
the serving path's batch spans, feeds it alone).
"""

from __future__ import annotations

import array
import json
import mmap
import numbers
import os
import struct
import threading
from typing import Dict, List, Optional

from repro.obs import trace as _trace_mod
from repro.obs.trace import _TID_META_PID, now_ns

DEFAULT_CAPACITY = 2048

# record layout (fields of one record, in order)
SEQ, PH, NAME, T0, T1, LANE_TID, LANE, TRACE, N_ATTRS = range(9)
ATTR0 = 9
MAX_ATTRS = 6
WIDTH = ATTR0 + 2 * MAX_ATTRS

_RECORD_BYTES = WIDTH * 8
_HEADER = struct.Struct(f"={ATTR0}q")
_THREE_ATTRS = struct.Struct("=6q")

PH_SPAN, PH_INSTANT = 0, 1
_PH_NAMES = ("X", "i")

# attribute value tags (the low 3 bits of an attribute key)
T_INT, T_STR, T_FLOAT, T_BOOL, T_NONE, T_JSON = range(1, 7)

# a string code: the interned string's index above SUFFIX_BITS, and a
# numeric suffix + 1 below them (0: none)
SUFFIX_BITS = 40
_SUFFIX_MASK = (1 << SUFFIX_BITS) - 1
_MAX_SUFFIX_DIGITS = 12             # < 2**40
MAX_STRINGS = 1 << 16
_OVERFLOW = 1                       # index of the "string table full" mark


class _Ring:
    """One thread's preallocated record ring.

    ``q`` is ``capacity * WIDTH`` int64 fields in an anonymous mapping
    (pages are touched only as records reach them); ``head`` is the next
    record to (over)write and ``seq`` the total records ever written — so
    the ring holds ``seq - min(seq, capacity)`` onwards.
    """

    __slots__ = ("tid", "lane", "capacity", "mem", "q", "head", "seq",
                 "lock", "_fd", "_fq")

    def __init__(self, tid: int, lane: str, capacity: int):
        self.tid = tid
        self.lane = lane
        self.capacity = capacity
        self.mem = mmap.mmap(-1, capacity * WIDTH * 8)
        self.q = memoryview(self.mem).cast("q")
        self.head = 0
        self.seq = 0
        self.lock = threading.Lock()
        # a float's bits, read through a second view of one 8-byte cell
        cell = bytearray(8)
        self._fd = memoryview(cell).cast("d")
        self._fq = memoryview(cell).cast("q")

    def put(self, ph: int, name: int, t0_ns: int, t1_ns: int,
            lane_tid: int, lane: int, trace: int, n: int,
            k0: int = 0, v0: int = 0, k1: int = 0, v1: int = 0,
            k2: int = 0, v2: int = 0) -> None:
        """Overwrite the oldest record with a record of up to three
        already-encoded attributes."""
        with self.lock:
            off = self.head * _RECORD_BYTES
            _HEADER.pack_into(self.mem, off, self.seq, ph, name, t0_ns, t1_ns,
                              lane_tid, lane, trace, n)
            if n:
                _THREE_ATTRS.pack_into(self.mem, off + _HEADER.size, k0, v0,
                                       k1, v1, k2, v2)
            self.head = self.head + 1 if self.head + 1 < self.capacity else 0
            self.seq = self.seq + 1

    def put_attrs(self, rec: "FlightRecorder", ph: int, name: int,
                  t0_ns: int, t1_ns: int, lane_tid: int, lane: int,
                  trace: int, attrs: Dict) -> None:
        """Overwrite the oldest record, encoding a caller's ``attrs``
        dict into the record's attribute fields."""
        q = self.q
        index = rec._index
        with self.lock:
            off = self.head * _RECORD_BYTES
            n = len(attrs)
            _HEADER.pack_into(self.mem, off, self.seq, ph, name, t0_ns, t1_ns,
                              lane_tid, lane, trace,
                              n if n <= MAX_ATTRS else MAX_ATTRS + 1)
            a = self.head * WIDTH + ATTR0
            end = a + 2 * MAX_ATTRS
            for key in attrs:
                if a == end:
                    break
                value = attrs[key]
                k = index.get(key)
                k = (rec.key(key) if k is None else k) << 3
                t = type(value)
                if t is int and -(1 << 63) <= value < (1 << 63):
                    q[a] = k | T_INT
                    q[a + 1] = value
                elif t is str:
                    q[a] = k | T_STR
                    q[a + 1] = rec.code(value)
                elif t is float:
                    self._fd[0] = value
                    q[a] = k | T_FLOAT
                    q[a + 1] = self._fq[0]
                elif t is bool:
                    q[a] = k | T_BOOL
                    q[a + 1] = 1 if value else 0
                elif value is None:
                    q[a] = k | T_NONE
                    q[a + 1] = 0
                elif (isinstance(value, numbers.Integral)
                      and -(1 << 63) <= int(value) < (1 << 63)):
                    q[a] = k | T_INT
                    q[a + 1] = int(value)
                elif isinstance(value, numbers.Real):
                    self._fd[0] = float(value)
                    q[a] = k | T_FLOAT
                    q[a + 1] = self._fq[0]
                else:
                    q[a] = k | T_JSON
                    q[a + 1] = rec.code(json.dumps(value, default=str))
                a += 2
            self.head = self.head + 1 if self.head + 1 < self.capacity else 0
            self.seq = self.seq + 1

    def rows(self, since: int = 0) -> tuple:
        """``(first_seq, rows)``: the held records with ``seq >= since``,
        oldest first, as a flat ``array('q')`` copy of ``WIDTH`` fields
        each; ``first_seq`` is the oldest held record's seq (above
        ``since`` when the ring overwrote part of what was asked for)."""
        with self.lock:
            held = min(self.seq, self.capacity)
            first = self.seq - held
            start = max(first, since)
            count = self.seq - start
            out = array.array("q")
            if count > 0:
                i = (self.head - count) % self.capacity
                j = i + count
                if j <= self.capacity:
                    out.frombytes(self.q[i * WIDTH:j * WIDTH].tobytes())
                else:
                    out.frombytes(self.q[i * WIDTH:].tobytes())
                    out.frombytes(
                        self.q[:(j - self.capacity) * WIDTH].tobytes())
            return first, out


class FlightRecorder:
    """Process-wide black box: per-thread rings + Chrome-trace dumps."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 name: str = "flight"):
        if capacity < 1:
            raise ValueError(f"flight capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.name = name
        self._lock = threading.Lock()
        self._rings: Dict[int, _Ring] = {}
        self._tls = threading.local()
        self._dumps = 0
        # the intern table: index -> string, and string -> index
        self._strings: List[Optional[str]] = [None, "?"]
        self._index: Dict[str, int] = {}
        self._intern_lock = threading.Lock()
        # the keys of record_fields, tagged as integers
        self._fields = tuple(self.key(k) << 3 | T_INT
                             for k in ("device", "bucket", "frames"))

    # -- the intern table ----------------------------------------------------

    def _intern(self, s: str) -> int:
        i = self._index.get(s)
        if i is not None:
            return i
        with self._intern_lock:
            i = self._index.get(s)
            if i is None:
                if len(self._strings) >= MAX_STRINGS:
                    return _OVERFLOW
                i = len(self._strings)
                self._strings.append(s)
                self._index[s] = i
            return i

    def key(self, s: str) -> int:
        """The intern index of an attribute key (no suffix split)."""
        return self._intern(s)

    def code(self, s: Optional[str]) -> int:
        """The integer code of ``s`` (0 for None)."""
        if s is None:
            return 0
        i = self._index.get(s)
        if i is not None:
            return i << SUFFIX_BITS
        head, sep, tail = s.rpartition("-")
        if (sep and tail.isascii() and tail.isdigit()
                and len(tail) <= _MAX_SUFFIX_DIGITS
                and (tail[0] != "0" or len(tail) == 1)):
            return ((self._intern(head + sep) << SUFFIX_BITS)
                    | (int(tail) + 1))
        return self._intern(s) << SUFFIX_BITS

    def decode(self, code: int) -> Optional[str]:
        """The string of a code (None for 0)."""
        if code == 0:
            return None
        s = self._strings[code >> SUFFIX_BITS]
        n = code & _SUFFIX_MASK
        return s if n == 0 else f"{s}{n - 1}"

    def _value(self, key: int, value: int):
        tag = key & 7
        if tag == T_INT:
            return value
        if tag == T_STR:
            return self.decode(value)
        if tag == T_FLOAT:
            return array.array("d", array.array("q", [value]).tobytes())[0]
        if tag == T_BOOL:
            return bool(value)
        if tag == T_JSON:
            return json.loads(self.decode(value))
        return None

    def attrs(self, row) -> Dict:
        """A record's attributes (``row``: its ``WIDTH`` fields)."""
        out = {}
        n = row[N_ATTRS]
        for j in range(min(n, MAX_ATTRS)):
            k = row[ATTR0 + 2 * j]
            out[self._strings[k >> 3]] = self._value(k, row[ATTR0 + 2 * j + 1])
        if n > MAX_ATTRS:
            out["attrs_truncated"] = True
        return out

    # -- recording (hot path) ----------------------------------------------

    def _ring(self) -> _Ring:
        """The calling thread's ring (registered on first use)."""
        ring = getattr(self._tls, "ring", None)
        if ring is not None:
            return ring
        tid = threading.get_ident()
        lane = threading.current_thread().name
        ring = _Ring(tid, lane, self.capacity)
        with self._lock:
            # a reused OS tid replaces the dead thread's ring: one ring
            # per live tid keeps per-ring seq contiguity meaningful
            self._rings[tid] = ring
        self._tls.ring = ring
        return ring

    def record_span(self, name: str, t0_ns: int, t1_ns: int,
                    trace_id: Optional[str] = None,
                    attrs: Optional[Dict] = None,
                    lane_tid: Optional[int] = None,
                    lane: Optional[str] = None) -> None:
        trace = self.code(trace_id)
        # a request's lane is named by its trace id: code it once
        lane_code = trace if lane is trace_id else self.code(lane)
        if attrs:
            self._ring().put_attrs(self, PH_SPAN, self.code(name), t0_ns,
                                   t1_ns, lane_tid or 0, lane_code, trace,
                                   attrs)
        else:
            self._ring().put(PH_SPAN, self.code(name), t0_ns, t1_ns,
                             lane_tid or 0, lane_code, trace, 0)

    def record_event(self, name: str, t_ns: Optional[int] = None,
                     trace_id: Optional[str] = None,
                     attrs: Optional[Dict] = None) -> None:
        if t_ns is None:
            t_ns = now_ns()
        if attrs:
            self._ring().put_attrs(self, PH_INSTANT, self.code(name), t_ns,
                                   t_ns, 0, 0, self.code(trace_id), attrs)
        else:
            self._ring().put(PH_INSTANT, self.code(name), t_ns, t_ns, 0, 0,
                             self.code(trace_id), 0)

    def record_fields(self, name: str, t0_ns: int, t1_ns: int,
                      device: int = -1, bucket: int = -1,
                      frames: int = -1) -> None:
        """A span whose attributes are the serving path's integer fields;
        a negative field is left out. Nothing is allocated: the field
        keys were interned when the recorder was made."""
        kd, kb, kf = self._fields
        if device >= 0 and bucket >= 0 and frames >= 0:
            self._ring().put(PH_SPAN, self.code(name), t0_ns, t1_ns, 0, 0, 0,
                             3, kd, device, kb, bucket, kf, frames)
            return
        # pack the present fields to the front
        n = 0
        k0 = v0 = k1 = v1 = k2 = v2 = 0
        for k, v in ((kd, device), (kb, bucket), (kf, frames)):
            if v < 0:
                continue
            if n == 0:
                k0, v0 = k, v
            elif n == 1:
                k1, v1 = k, v
            else:
                k2, v2 = k, v
            n += 1
        self._ring().put(PH_SPAN, self.code(name), t0_ns, t1_ns, 0, 0, 0, n,
                         k0, v0, k1, v1, k2, v2)

    # -- reading -------------------------------------------------------------

    def seqs(self) -> Dict[int, int]:
        """Each ring's total records written so far, by ring (thread) id:
        a reader's mark of where a part of the run begins."""
        with self._lock:
            rings = list(self._rings.values())
        return {r.tid: r.seq for r in rings}

    def rows(self, since: Optional[Dict[int, int]] = None) -> List[Dict]:
        """Every ring's held records, as raw integer rows: a list of
        ``{"tid", "lane", "seq", "first_seq", "since", "wrapped",
        "rows"}`` with ``rows`` an ``array('q')`` of ``WIDTH`` fields a
        record, oldest first. With ``since`` (a :meth:`seqs` mark), only
        the records written after it, and ``wrapped`` says whether the
        ring overwrote any of them (a ring made after the mark counts
        from 0)."""
        with self._lock:
            rings = list(self._rings.values())
        out = []
        for r in rings:
            start = (since or {}).get(r.tid, 0) if since is not None else 0
            first, rows = r.rows(start)
            out.append({"tid": r.tid, "lane": r.lane, "seq": r.seq,
                        "first_seq": first, "since": start,
                        "wrapped": first > start, "rows": rows})
        return out

    # -- dumping -----------------------------------------------------------

    def dump(self, reason: Optional[str] = None) -> Dict:
        """All retained history as Chrome-trace JSON (a plain dict).

        Same shape as :meth:`obs.Trace.to_chrome`: complete ("X") and
        instant ("i") events with microsecond ``ts`` relative to the
        dump epoch (the earliest retained timestamp), ``thread_name``
        metadata per lane, and per-record ``args`` carrying the ring's
        ``seq``/``ring`` so ``check_trace.py --flight`` can prove the
        retained history is gap-free.
        """
        with self._lock:
            self._dumps = self._dumps + 1
        ring_rows = self.rows()

        epoch = None
        for rr in ring_rows:
            rows = rr["rows"]
            for b in range(0, len(rows), WIDTH):
                if epoch is None or rows[b + T0] < epoch:
                    epoch = rows[b + T0]
        if epoch is None:
            epoch = now_ns()

        events = []
        lanes: Dict[int, str] = {}
        total = 0
        dropped = 0
        for rr in ring_rows:
            rows = rr["rows"]
            held = len(rows) // WIDTH
            total += held
            dropped += max(0, rr["seq"] - held)
            lanes.setdefault(rr["tid"], f"flight:{rr['lane']}")
            for b in range(0, len(rows), WIDTH):
                row = rows[b:b + WIDTH]
                tid = rr["tid"]
                if row[LANE_TID]:
                    tid = row[LANE_TID]
                    if row[LANE]:
                        lanes.setdefault(tid, self.decode(row[LANE]))
                args = self.attrs(row)
                args["seq"] = row[SEQ]
                args["ring"] = rr["tid"]
                if row[TRACE]:
                    args["trace_id"] = self.decode(row[TRACE])
                name = self.decode(row[NAME])
                ph = _PH_NAMES[row[PH]]
                ev = {"name": name, "ph": ph,
                      "cat": name.split(".", 1)[0],
                      "pid": _TID_META_PID, "tid": tid,
                      "ts": (row[T0] - epoch) / 1e3, "args": args}
                if ph == "X":
                    ev["dur"] = (row[T1] - row[T0]) / 1e3
                else:
                    ev["s"] = "t"
                events.append(ev)

        meta = [{"name": "thread_name", "ph": "M", "pid": _TID_META_PID,
                 "tid": tid, "args": {"name": lane}}
                for tid, lane in sorted(lanes.items(), key=lambda kv: kv[0])]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "otherData": {"flight": self.name,
                              "reason": reason,
                              "capacity": self.capacity,
                              "rings": len(ring_rows),
                              "records": total,
                              "dropped_total": dropped,
                              "strings": len(self._strings)}}

    def stats(self) -> Dict:
        with self._lock:
            rings = list(self._rings.values())
            dumps = self._dumps
        retained = sum(min(r.seq, self.capacity) for r in rings)
        total = sum(r.seq for r in rings)
        return {"rings": len(rings), "capacity": self.capacity,
                "retained": retained, "recorded_total": total,
                "dropped_total": total - retained, "dumps": dumps,
                "strings": len(self._strings)}


# ---------------------------------------------------------------------------
# Process-global installation (mirrors trace.enable/disable)
# ---------------------------------------------------------------------------

_install_lock = threading.Lock()


def install(recorder: Optional[FlightRecorder] = None) -> FlightRecorder:
    """Install ``recorder`` (or a fresh one) as the process flight box."""
    with _install_lock:
        if recorder is None:
            recorder = FlightRecorder()
        _trace_mod._flight = recorder
        return recorder


def uninstall() -> Optional[FlightRecorder]:
    """Remove the flight recorder; returns it (for a final dump) or None."""
    with _install_lock:
        recorder = _trace_mod._flight
        _trace_mod._flight = None
        return recorder


def get_flight() -> Optional[FlightRecorder]:
    """The installed flight recorder, if any."""
    return _trace_mod._flight


def install_default() -> Optional[FlightRecorder]:
    """The import-time default: on unless ``REPRO_FLIGHT=off``.

    ``REPRO_FLIGHT_SLOTS`` overrides the per-thread capacity. Called
    once from ``repro.obs.__init__``; explicit ``install()``/
    ``uninstall()`` calls afterwards win.
    """
    mode = os.environ.get("REPRO_FLIGHT", "").strip().lower()
    if mode in ("off", "0", "false", "no"):
        return None
    capacity = int(os.environ.get("REPRO_FLIGHT_SLOTS", DEFAULT_CAPACITY))
    return install(FlightRecorder(capacity=capacity))
