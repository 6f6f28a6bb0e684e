"""The per-layer readers on a hand-made context: what each reads, and
that each returns nothing where it finds nothing to read."""

import pytest

import cells
from conftest import ROOT


def _ctx(chips=1, traced=True):
    """A traced run's context; ``traced=False``: a run with no traced
    parts (the profiler's start took the window, or no trace was asked)."""
    return {
        "chips": chips,
        "peak": {"int8_ops_per_s": 400e12},
        "ops_per_frame": 2e9,
        "least_time_per_frame_s": 1e-5,
        "parts": {
            "window": {"frames": 1900, "slots": 2000, "batches": 140,
                       "seconds": 20.0},
            "host": ({"frames": 900, "slots": 1000, "batches": 70,
                      "seconds": 10.0} if traced else None),
            "trace": ({"frames": 200, "slots": 256, "batches": 16,
                       "seconds": 1.0} if traced else None)},
        "spans": [{"name": "serve.request.queue_wait", "t0_ns": 0,
                   "t1_ns": 1_000_000},
                  {"name": "serve.request.queue_wait", "t0_ns": 5,
                   "t1_ns": 3_000_005},
                  {"name": "serve.request.device", "t0_ns": 0,
                   "t1_ns": 9_000_000}],
        "trace": ({"busy_s": 0.5, "idle_share": 0.75} if traced else None),
    }


def _read(name, ctx):
    return cells.metric_reader(ROOT, name)(ctx)


@pytest.mark.parametrize("chips", [1, 4])
def test_program_roofline_counts_every_chip_busy(chips):
    # 200 frames x 10 us least time over 0.5 s busy on each chip
    assert _read("program_roofline", _ctx(chips)) == \
        pytest.approx(100.0 * 2e-3 / (0.5 * chips))


@pytest.mark.parametrize("chips", [1, 4])
def test_mfu_over_the_peak_of_every_chip(chips):
    # the untraced host part's 90 frames/s (not the traced part's 200) x
    # 2 GOP over 400 TOP/s per chip
    assert _read("mfu", _ctx(chips)) == \
        pytest.approx(100.0 * 90 * 2e9 / (400e12 * chips))


def test_serving_and_device_readers():
    ctx = _ctx()
    assert _read("batch_fill", ctx) == pytest.approx(90.0)
    assert _read("queue_wait_ms", ctx) == pytest.approx(2.0)
    for name in ("device_idle_share.offline", "device_idle_share.stream"):
        assert _read(name, ctx) == pytest.approx(75.0)


@pytest.mark.parametrize("name", ["program_roofline", "mfu", "batch_fill",
                                  "device_idle_share.offline",
                                  "device_idle_share.stream"])
def test_nothing_to_read_gives_nothing(name):
    assert _read(name, _ctx(traced=False)) is None


def test_no_spans_and_no_slots_give_nothing():
    ctx = _ctx()
    ctx["spans"] = []
    ctx["parts"]["host"] = {"frames": 0, "slots": 0, "batches": 0,
                            "seconds": 10.0}
    assert _read("queue_wait_ms", ctx) is None
    assert _read("batch_fill", ctx) is None
