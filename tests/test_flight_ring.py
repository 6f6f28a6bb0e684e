"""The flight recorder's numeric ring: integer records that keep nothing
of the caller's alive and create no object the garbage collector tracks,
dumps that keep their Chrome-trace shape, and wrap that a reader can
detect from ``seq``."""

import gc
import importlib.util
import json
import threading
import weakref
from pathlib import Path

import pytest

from repro import obs
from repro.obs import flight as flight_mod

ROOT = Path(__file__).resolve().parent.parent


def _check_trace():
    spec = importlib.util.spec_from_file_location(
        "check_trace", ROOT / "scripts" / "check_trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def ring():
    """A fresh recorder for the test; the previous one restored."""
    prev = obs.get_flight()
    recorder = obs.install(obs.FlightRecorder(capacity=256, name="test"))
    try:
        yield recorder
    finally:
        if prev is not None:
            obs.install(prev)
        else:
            obs.uninstall()


def _tracked_growth(record, n=10_000):
    """(objects the collector tracks, gen-0 count) added by ``n`` calls of
    ``record(i)``, measured with the collector paused."""
    for i in range(64):                 # first use: ring, interned names
        record(i)
    gc.collect()
    gc.disable()
    try:
        objects0, count0 = len(gc.get_objects()), gc.get_count()[0]
        for i in range(n):
            record(i)
        return (len(gc.get_objects()) - objects0,
                gc.get_count()[0] - count0)
    finally:
        gc.enable()


def test_batch_spans_add_no_tracked_object(ring):
    grown, count = _tracked_growth(lambda i: obs.span_ns(
        "serve.batch.stage", i, i + 5, device=0, bucket=8, frames=i % 8))
    # nothing kept, and nothing made and freed per record either (the
    # measurement itself may count one or two)
    assert grown == 0 and count < 10


def test_spans_with_attrs_keep_no_object(ring):
    """A caller's attrs and trace ids are coded into integers: the ring
    keeps none of them, so the heap stays the size it was."""
    def record(i):
        obs.span_at("serve.request.device", i * 1e-6, i * 1e-6 + 1e-6,
                    attrs={"program": "edge", "frames": 1, "bucket": 4,
                           "device": 0},
                    trace_id=f"edge/req-{i}", lane_tid=1 << 20 | i,
                    lane=f"edge/req-{i}")
    grown, _ = _tracked_growth(record)
    assert grown == 0


def test_a_record_keeps_no_caller_object_alive(ring):
    class Attrs(dict):
        pass

    attrs = Attrs(device=1, error="WorkerError")
    alive = weakref.ref(attrs)
    obs.event("t.failure", attrs=attrs)
    del attrs
    assert alive() is None
    ev = next(e for e in ring.dump()["traceEvents"]
              if e["name"] == "t.failure")
    assert ev["args"]["device"] == 1 and ev["args"]["error"] == "WorkerError"


def test_request_ids_do_not_grow_the_intern_table(ring):
    for i in range(2000):
        obs.event("serve.submit", attrs={"program": "edge", "frames": 1},
                  trace_id=f"edge/req-{i}")
    assert ring.stats()["strings"] < 16
    recs = [e for e in ring.dump()["traceEvents"] if e["ph"] == "i"]
    assert recs[-1]["args"]["trace_id"] == "edge/req-1999"


@pytest.mark.parametrize("value", [
    7, -3, 2**62, "WorkerError", "req-007", 0.25, -1e-300, True, False,
    None, [16, 16, 3], ["conv1", "conv2"]])
def test_attribute_values_round_trip(ring, value):
    obs.event("t.value", attrs={"v": value})
    ev = next(e for e in ring.dump()["traceEvents"]
              if e["name"] == "t.value")
    assert ev["args"]["v"] == value and type(ev["args"]["v"]) is type(value)


def test_attributes_beyond_the_record_are_marked(ring):
    obs.event("t.many", attrs={f"k{i}": i for i in range(9)})
    args = next(e for e in ring.dump()["traceEvents"]
                if e["name"] == "t.many")["args"]
    assert [args[f"k{i}"] for i in range(flight_mod.MAX_ATTRS)] == \
        list(range(flight_mod.MAX_ATTRS))
    assert args["attrs_truncated"] is True


def test_dump_keeps_its_shape_and_passes_the_validator(ring, tmp_path):
    with obs.span("t.outer", attrs={"model": "lenet"}, trace_id="req-7"):
        obs.span_ns("serve.batch.pad", obs.now_ns(), obs.now_ns(),
                    bucket=4, frames=3)
        obs.event("t.mark")
    obs.span_at("t.retro", 1.0, 2.0, trace_id="req-8", lane_tid=4242,
                lane="req-8")
    d = ring.dump(reason="unit")
    assert set(d) == {"traceEvents", "displayTimeUnit", "otherData"}
    assert d["otherData"]["reason"] == "unit"
    assert d["otherData"]["records"] == 4
    recs = {e["name"]: e for e in d["traceEvents"] if e["ph"] != "M"}
    assert recs["t.outer"]["args"]["model"] == "lenet"
    assert recs["t.outer"]["args"]["trace_id"] == "req-7"
    assert recs["serve.batch.pad"]["args"] == {
        "bucket": 4, "frames": 3, "seq": 0,
        "ring": recs["serve.batch.pad"]["args"]["ring"]}
    assert recs["t.mark"]["ph"] == "i" and recs["t.mark"]["s"] == "t"
    assert recs["t.retro"]["tid"] == 4242
    assert recs["t.retro"]["dur"] == pytest.approx(1e6)
    assert {"name": "thread_name", "ph": "M", "pid": 1, "tid": 4242,
            "args": {"name": "req-8"}} in d["traceEvents"]
    path = tmp_path / "flight.json"
    path.write_text(json.dumps(d))
    assert _check_trace().flight_check(str(path)) == []


def test_wrap_is_detected_from_seq():
    prev = obs.get_flight()
    rec = obs.install(obs.FlightRecorder(capacity=8))
    try:
        for i in range(5):
            obs.span_ns("t.tick", i, i + 1, frames=i)
        mark = rec.seqs()
        for i in range(3):
            obs.span_ns("t.tick", i, i + 1, frames=5 + i)
        (held,) = rec.rows(mark)
        assert not held["wrapped"] and len(held["rows"]) == \
            3 * flight_mod.WIDTH
        for i in range(6):                  # 5 + 3 + 6 > 8: overwritten
            obs.span_ns("t.tick", i, i + 1, frames=8 + i)
        (held,) = rec.rows(mark)
        assert held["wrapped"]
        assert held["first_seq"] == held["seq"] - 8 > mark[held["tid"]]
        first = held["rows"][flight_mod.SEQ::flight_mod.WIDTH]
        assert list(first) == list(range(6, 14))
    finally:
        obs.uninstall()
        if prev is not None:
            obs.install(prev)


def test_a_ring_made_after_the_mark_counts_from_zero(ring):
    mark = ring.seqs()
    t = threading.Thread(target=lambda: obs.span_ns("t.late", 1, 2),
                         name="late")
    t.start()
    t.join()
    late = [r for r in ring.rows(mark) if r["lane"] == "late"]
    assert len(late) == 1 and late[0]["since"] == 0
    assert not late[0]["wrapped"]
