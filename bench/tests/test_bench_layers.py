"""Layer kinds are files of their own (``bench/layers/<kind>.py``), found
by the ``kind`` a configuration's entry names; the harness's model,
reference and work counts only walk the layer list.

- The reference gives the outputs it gave before the kinds were files, bit
  for bit (``data/reference_golden.npz``: ``reference.logits`` on the CPU,
  8 frames of each seed, chunk 8, at that time).
- An unknown kind fails naming its missing file; every kind a configuration
  of ``BENCHMARK.json`` uses has one.
- The reference imports nothing of the program under test.
- The built-in ``conv`` kind beyond stride 1 SAME: its reference matches
  the program, and its work is counted at the program's output sizes.
"""

import copy
import json
import shutil
import sys

import numpy as np
import pytest

import cells
import model
import reference
import work
from conftest import ROOT

TINY = ROOT / "bench" / "tests" / "data" / "tiny-ca.json"
GOLDEN = ROOT / "bench" / "tests" / "data" / "reference_golden.npz"
CONFIGS = {"tiny-ca": TINY,
           "vgg9ca-32-mx43": ROOT / "bench" / "configs" / "vgg9ca-32-mx43.json"}


def _load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(CONFIGS))
@pytest.mark.parametrize("seed", [7, 2**33 + 5])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_reference_matches_its_golden_output(name, seed, dtype):
    cfg = _load(CONFIGS[name])
    params = model.make_params(cfg, seed)
    frames = model.make_frames(cfg, 8, seed)
    got = reference.logits(cfg, params, frames, dtype, chunk=8)
    want = np.load(GOLDEN)[f"{name}/{seed}/{dtype}"]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


def test_unknown_kind_names_its_missing_file():
    cfg = _load(TINY)
    cfg["layers"][1]["kind"] = "bottleneck9"
    with pytest.raises(KeyError, match="bench/layers/bottleneck9.py"):
        cells.layer_kind(ROOT, "bottleneck9")
    for fn in (model.param_count, model.program_layers, work.layers):
        with pytest.raises(KeyError, match="bench/layers/bottleneck9.py"):
            fn(cfg)


def test_every_configured_kind_has_its_file():
    bench = cells.load_benchmark(ROOT)
    cfgs = [cells.config(ROOT, bench, c["name"]) for c in bench["configs"]]
    kinds = {l["kind"] for cfg in cfgs + [_load(TINY)]
             for l in cfg["layers"]}
    assert kinds >= {"ca", "conv", "flatten", "dense"}
    for kind in kinds:
        mod = cells.layer_kind(ROOT, kind)
        for face in ("weights", "program", "reference", "work"):
            assert callable(getattr(mod, face)), (kind, face)


def test_reference_imports_nothing_of_the_program(tmp_path, monkeypatch):
    """With ``repro`` unimportable, the reference still runs: the kind
    files (loaded afresh from a copy, so nothing is cached) import the
    program only inside their ``program`` face."""
    shutil.copytree(ROOT / "bench" / "layers", tmp_path / "bench" / "layers",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = _load(TINY)
    params = model.make_params(cfg, 3)
    frames = model.make_frames(cfg, 4, 3)
    want = reference.logits(cfg, params, frames, chunk=4)
    for mod in [m for m in sys.modules if m == "repro" or
                m.startswith("repro.")]:
        monkeypatch.delitem(sys.modules, mod)
    monkeypatch.setitem(sys.modules, "repro", None)
    with pytest.raises(ImportError):
        import repro.core.accelerator  # noqa: F401
    got = reference.logits(cfg, params, frames, chunk=4, root=tmp_path)
    assert got.tobytes() == want.tobytes()


def _strided():
    """tiny-ca with a stride-2 SAME conv (8x8 -> 4x4, padded 0 before and
    1 after) and a VALID conv (4x4 -> 2x2)."""
    cfg = _load(TINY)
    cfg["name"] = "tiny-strided"
    conv1, conv2 = cfg["layers"][1], cfg["layers"][2]
    conv1.update(stride=2)
    conv2.update(padding="VALID", pool=None)
    cfg["layers"][4].update(fan_in=2 * 2 * 16)
    return cfg


def _program_out(cfg, params, frames):
    from repro.core.program import Options
    exe = model.make_program(cfg, params).compile(
        Options(scheme=model.scheme(cfg)).resolve())
    return np.asarray(exe.run_per_frame(frames))


def test_strided_and_valid_convs_match_the_program():
    import check
    cfg = _strided()
    params = model.make_params(cfg, 11)
    frames = model.make_frames(cfg, 8, 11)
    out = _program_out(cfg, params, frames)
    ref = reference.logits(cfg, params, frames, chunk=8)
    assert out.shape == ref.shape == (8, 10)
    assert check.logit_err(out, ref) <= cfg["check"]["logit_err_limit"]


@pytest.mark.parametrize("upto", [2, 3])
def test_conv_work_at_the_programs_output_sizes(upto):
    """Cut after each conv, the program serves an image: its shape is the
    one the conv kind's work face gives, the work is counted at it, and the
    reference's image matches the program's."""
    import check
    full = _strided()
    cfg = copy.deepcopy(full)
    cfg["layers"] = full["layers"][:upto]
    params = model.make_params(cfg, 5)
    frames = model.make_frames(cfg, 4, 5)
    out = _program_out(cfg, params, frames)
    layer = cfg["layers"][-1]
    hwc = tuple(cfg["input_hwc"])
    for l in cfg["layers"]:
        rows, hwc = cells.layer_kind(ROOT, l["kind"]).work(
            hwc, l, {"a_bits": 4, "w_bits": cells.weight_bits(ROOT, cfg)})
    assert out.shape[1:] == hwc
    h, w, c = hwc
    k = layer["kernel"]
    assert work.layers(cfg)[-1]["macs"] == h * w * layer["c_in"] * c * k * k
    ref = reference.logits(cfg, params, frames, chunk=4)
    assert ref.shape == out.shape
    assert check.logit_err(out.reshape(4, -1), ref.reshape(4, -1)) <= \
        cfg["check"]["logit_err_limit"]
