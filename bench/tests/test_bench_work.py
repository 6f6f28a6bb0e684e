"""Dense work counts of the two configurations, from their layer lists."""

import json

import pytest

import work
from conftest import ROOT


def _cfg(name):
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        return json.load(f)


def _macs(cfg, prefix):
    return sum(l["macs"] for l in work.layers(cfg)
               if l["name"].startswith(prefix))


def test_vgg16_macs():
    cfg = _cfg("vgg16-224-mx43")
    # 13 3x3 convs at 224, 112, 56, 28, 14 (Simonyan & Zisserman's 15.3 G)
    assert _macs(cfg, "conv") == 15_346_630_656
    # 25088x4096 + 4096x4096 + 4096x1000
    assert _macs(cfg, "fc") == 123_633_664
    assert work.ops_per_frame(cfg) == 2 * (15_346_630_656 + 123_633_664)


def test_vgg9_macs():
    cfg = _cfg("vgg9ca-32-mx43")
    # CA to 16x16x1, then convs at 16, 16, 8, 8, 4, 4
    conv = (16 * 16 * 1 * 64 * 9 + 16 * 16 * 64 * 64 * 9
            + 8 * 8 * 64 * 128 * 9 + 8 * 8 * 128 * 128 * 9
            + 4 * 4 * 128 * 256 * 9 + 4 * 4 * 256 * 256 * 9)
    assert _macs(cfg, "conv") == conv == 37_896_192
    assert _macs(cfg, "fc") == 1024 * 512 + 512 * 512 + 512 * 100
    assert _macs(cfg, "ca") == 16 * 16 * 2 * 2 * 3


def test_param_counts_match_the_files():
    import model
    for name in ("vgg16-224-mx43", "vgg9ca-32-mx43"):
        cfg = _cfg(name)
        assert model.param_count(cfg) == cfg["params"]
    assert _cfg("vgg16-224-mx43")["params"] == 138_357_544


@pytest.mark.parametrize("batch", [1, 8, 64])
def test_least_time_bounds(batch):
    """Least time is at least the compute bound and at least the time to
    read the weights once."""
    cfg = _cfg("vgg16-224-mx43")
    peak = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    t = work.least_time_s(cfg, batch, peak)
    assert t >= batch * work.ops_per_frame(cfg) / peak["int8_ops_per_s"]
    w_bytes = sum(l["weight_bytes"] for l in work.layers(cfg))
    assert t >= w_bytes / peak["hbm_bytes_per_s"]


V5E = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("path,ops,params,least", [
    ("configs/vgg16-224-mx43.json", 30_940_528_640, 138_357_544,
     {8: 0.0006995687019760087}),
    ("configs/vgg9ca-32-mx43.json", 77_473_792, 1_983_012,
     {64: 1.4366099253269208e-05, 16: 3.883788549581038e-06}),
    ("tests/data/tiny-ca.json", 175_232, 9_802, {}),
])
def test_counts_are_pinned(path, ops, params, least):
    """The counts the per-layer metrics divide by, exactly as the layer
    list gave them before the layer kinds were files of their own."""
    import model
    with open(ROOT / "bench" / path) as f:
        cfg = json.load(f)
    assert work.ops_per_frame(cfg) == ops
    assert model.param_count(cfg) == params
    for batch, t in least.items():
        assert work.least_time_s(cfg, batch, V5E) == t
