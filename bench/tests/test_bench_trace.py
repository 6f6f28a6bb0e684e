"""The reduction from a profiler trace to busy time, idle share, op times
and idle gaps."""

import json

import pytest

import trace_reduce as T
from conftest import ROOT

DATA = ROOT / "bench" / "tests" / "data"


def _marks(lo, hi):
    """An opening mark ending at ``lo``, a closing one starting at ``hi``,
    each as the mark program's module and one op of it."""
    return {"name": T.MARK_LINE, "events": [
        [T.WINDOW_MARK, lo - 30, 30], [T.WINDOW_MARK, lo - 20, 10],
        [T.WINDOW_MARK, hi, 30], [T.WINDOW_MARK, hi + 5, 10]]}


def _trace():
    """Windows [1000, 11000) ns; device 0 ops overlap; device 1 idles
    more, in a window of its own."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["add.1", 970, 10],           # the opening mark's op
                ["fusion.1", 900, 200],       # clipped to [1000, 1100)
                ["conv", 1050, 150],          # overlaps fusion.1
                ["conv", 1700, 100],
                ["fusion.1", 1950, 100]]},
            _marks(1000, 11000)]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["conv", 1000, 100]]},
            _marks(1000, 21000)]},
    ]}


def test_union_and_gaps():
    busy = T.union([(5, 10), (0, 3), (2, 4), (9, 12)], 1, 11)
    assert busy == [(1, 4), (5, 11)]
    assert T.gaps(busy, 0, 15) == [(0, 1), (4, 5), (11, 15)]


def test_busy_idle_and_gaps_of_one_device():
    r = T.reduce(_trace(), n_devices=1)
    # busy: [1000, 1200) + [1700, 1800) + [1950, 2050) = 400 ns
    assert r["window_s"] == pytest.approx(1e-5)
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["idle_share"] == pytest.approx(0.96)
    ops = dict(r["device_ops"])
    assert ops["conv"] == pytest.approx(250e-9)
    assert ops["fusion.1"] == pytest.approx(200e-9)
    assert "add.1" not in ops
    # gaps, longest first, each named by the op that ran last before it
    assert r["idle_gaps"] == [["after fusion.1", pytest.approx(8950e-9)],
                              ["after conv", pytest.approx(500e-9)],
                              ["after conv", pytest.approx(150e-9)]]


def test_busy_averages_over_the_devices_used():
    r = T.reduce(_trace(), n_devices=2)
    assert r["busy_s_per_device"] == pytest.approx([400e-9, 100e-9])
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["window_s"] == pytest.approx(1.5e-5)


def test_a_window_needs_both_marks():
    tr = _trace()
    tr["planes"][0]["lines"][1]["events"] = [[T.WINDOW_MARK, 970, 30]]
    with pytest.raises(ValueError, match="marks"):
        T.reduce(tr, n_devices=1)


def test_cut_keeps_the_start_of_each_window():
    part = T.cut(_trace(), 1.2e-6)
    r = T.reduce(part, n_devices=1)
    # [1000, 2200): the ops that start in it (fusion.1 at 900 does not)
    assert r["window_s"] == pytest.approx(1.2e-6)
    assert r["busy_s"] == pytest.approx(350e-9)


def test_no_device_plane_is_an_error():
    tr = _trace()
    tr["planes"] = [{"name": "/host:CPU", "lines": []}]
    with pytest.raises(ValueError, match="no TPU device plane"):
        T.reduce(tr, n_devices=1)


def test_recorded_chip_trace():
    """The first 20 ms of a VGG16 trace recorded on a TPU v5e (one chip,
    batch 8, device planes only, the window marked on the device): the
    device ran most of it, and its ops are the program's kernels and
    fusions."""
    with open(DATA / "vgg16_trace_slice.json") as f:
        rec = json.load(f)
    r = T.reduce(rec["trace"], n_devices=1)
    assert r["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert r["idle_share"] == pytest.approx(rec["expect"]["idle_share"],
                                            rel=1e-9)
    assert [n for n, _ in r["device_ops"]][:3] == \
        rec["expect"]["top_ops"]
    assert 0 < r["busy_s"] <= r["window_s"]
