"""The harness around a run: it refuses to run off the chip, finds a
cell's pieces by name, and its check fails a broken timed path.

The runs here skip the harness's look for a chip (they call ``run_cell``
on the CPU, where the options resolve to the reference backend) and drive
the rest of a run at a size a test holds: ``tiny-ca`` has the layer kinds
of the VGG9 configuration (compressive acquisition, convs with a max
pool, dense layers), served through ``repro.serve.Server``.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import cells
import model
from conftest import ROOT

TINY = ROOT / "bench" / "tests" / "data" / "tiny-ca.json"
TRAFFIC = {"loop": "closed", "outstanding": 8, "frames_per_request": 1,
           "distinct_frames": 32, "warm_requests": 8,
           "server": {"max_batch": 4, "batch_buckets": [4],
                      "max_wait_ms": 2.0, "max_inflight": 2,
                      "max_queue": 64, "devices": 1}}


def _run_cell(hooks=None, seed=2**33 + 5, traffic=TRAFFIC, cfg=None,
              root=ROOT):
    import run
    from repro.core.program import Options
    if cfg is None:
        with open(TINY) as f:
            cfg = json.load(f)
    bench = cells.load_benchmark(ROOT)
    options = Options(scheme=model.scheme(cfg)).resolve()
    peak = cells.peaks(ROOT, "TPU v5 lite")
    return run.run_cell(bench, {"name": "vgg9-offline"}, cfg, traffic, seed,
                        0.5, False, options, jax.devices()[:1], peak,
                        hooks=hooks, root=root)


def _env():
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_no_result_without_a_tpu():
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "vgg9-stream", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_no_result_from_the_benchmark_files_alone(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vgg16-offline",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    assert cells.peaks(ROOT, "TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="peaks.json"):
        cells.peaks(ROOT, "TPU v9 imaginary")


def test_pieces_added_by_name_are_found(tmp_path):
    """A later cell brings a config, a traffic mix and a metric reader as
    new files plus BENCHMARK.json entries, and edits nothing."""
    bench = cells.load_benchmark(ROOT)
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    shutil.copy(ROOT / "bench" / "peaks.json", tmp_path / "bench")
    shutil.copy(TINY, tmp_path / "bench" / "configs" / "tiny-ca.json")
    (tmp_path / "bench" / "traffic" / "burst9.json").write_text(
        json.dumps(TRAFFIC))
    (tmp_path / "bench" / "metrics" / "answer.late.py").write_text(
        "def read(ctx):\n    return ctx['x'] * 2\n")
    bench["configs"].append({"name": "tiny-ca", "source": "test",
                             "file": "bench/configs/tiny-ca.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.burst", "config": "tiny-ca",
                               "traffic": "burst9", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "answer.late", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "serving", "moves": "frames_per_s",
                               "workloads": ["tiny.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    bench = cells.load_benchmark(tmp_path)
    cell = cells.cell(bench, "tiny.burst")
    assert cells.config(tmp_path, bench, cell["config"])["name"] == "tiny-ca"
    assert cells.traffic(tmp_path, cell["traffic"]) == TRAFFIC
    names = [m["name"] for m in cells.per_layer_metrics(bench, "tiny.burst")]
    assert names == ["answer.late"]
    assert cells.metric_reader(tmp_path, "answer.late")({"x": 3}) == 6
    assert [m["name"] for m in cells.end_to_end_metrics(
        bench, "tiny.burst")] == ["frames_per_s", "setup_s"]
    with pytest.raises(KeyError):
        cells.traffic(tmp_path, "missing")


def test_every_listed_metric_has_a_reader():
    bench = cells.load_benchmark(ROOT)
    for m in bench["per_layer"]:
        assert callable(cells.metric_reader(ROOT, m["name"]))
    for w in bench["workloads"]:
        cfg = cells.config(ROOT, bench, w["config"])
        assert cfg["check"]["logit_err_limit"] is not None
        cells.traffic(ROOT, w["traffic"])


def test_sound_run_is_correct():
    r = _run_cell()
    assert r["correct"], r["checks"]
    assert r["checks"]["compared"]["value"] > 8
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"frames_per_s", "setup_s"}
    assert r["metrics"]["frames_per_s"]["value"] > 0


def _altered(name, device, frames, bucket, default):
    out = default()
    return out.at[0, 0].add(jnp.abs(out[0]).max() * 0.05 + 1e-3)


def _half_left_out(name, device, frames, bucket, default):
    out = default()
    k = out.shape[0] // 2
    return jnp.concatenate([out[:out.shape[0] - k], out[:k]])


def _unchanged(name, device, frames, bucket, default):
    return jnp.zeros_like(default())


@pytest.mark.parametrize("fault", [_altered, _half_left_out, _unchanged])
def test_broken_timed_path_is_not_correct(fault):
    from repro.serve.server import Hooks
    r = _run_cell(hooks=Hooks(execute=fault))
    assert not r["correct"]
    assert r["checks"]["logit_err"]["value"] > \
        r["checks"]["logit_err"]["limit"]


def test_control_fails_the_limit():
    """The reference in bfloat16, in the program's place in the server,
    makes a whole run read not correct, above the limit, on every seed."""
    import control
    with open(TINY) as f:
        cfg = json.load(f)
    for seed in (1, 2**40 + 1):
        r = _run_cell(hooks=control.hooks(cfg, seed), seed=seed)
        assert not r["correct"]
        assert r["checks"]["logit_err"]["value"] > \
            r["checks"]["logit_err"]["limit"]
        assert r["checks"]["compared"]["value"] > 8


# A block kind as a later configuration would add it: one file, two conv
# tensors, each conv requantized. The altered copy drops the second
# requant, so the next conv is given float values where the program gives
# it codes.
PAIR_KIND = """
def _convs(layer):
    n, a, m, b = layer["name"], layer["c_in"], layer["c_mid"], layer["c_out"]
    return [(n + ".a", a, m), (n + ".b", m, b)]


def weights(layer):
    return [(name, (3, 3, ci, co)) for name, ci, co in _convs(layer)]


def program(layer):
    from repro.core.accelerator import ConvSpec
    return tuple(ConvSpec(name, ci, co) for name, ci, co in _convs(layer))


def reference(x, scale, layer, params, q):
    import jax
    import jax.numpy as jnp
    for i, (name, _, _) in enumerate(_convs(layer)):
        w, s = q.weight(params, name)
        acc = jax.lax.conv_general_dilated(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), (1, 1),
            ((1, 1), (1, 1)), dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)
        out = (q.no_fma(acc.astype(q.dtype) * (scale * s.reshape(1, 1, 1, -1)))
               + params[name]["b"].astype(q.dtype))
        y = jnp.maximum(out, 0)
        x, scale = q.requant(y)
    return x, scale


def work(hwc, layer, q):
    h, w, _ = hwc
    return ([{"name": name, "macs": h * w * ci * co * 9,
              "weight_bytes": 9 * ci * co * q["w_bits"][name] / 8 + co * 4,
              "act_bytes": h * w * co * q["a_bits"] / 8}
             for name, ci, co in _convs(layer)], (h, w, layer["c_out"]))
"""
DROP_SECOND_REQUANT = (
    "        x, scale = q.requant(y)\n",
    "        x, scale = q.requant(y) if i == 0 else (y, jnp.ones_like(scale))\n")


def _pair_root(tmp_path, source):
    """A checkout whose layer kinds are the built-in ones plus ``pair``,
    and tiny-ca with its first conv replaced by a pair (1 -> 4 -> 8)."""
    shutil.copytree(ROOT / "bench" / "layers", tmp_path / "bench" / "layers",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench" / "layers" / "pair.py").write_text(source)
    with open(TINY) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-pair"
    cfg["layers"][1] = {"kind": "pair", "name": "pair", "c_in": 1,
                        "c_mid": 4, "c_out": 8}
    return cfg


def test_a_kind_added_as_one_file_is_served_and_checked(tmp_path):
    cfg = _pair_root(tmp_path, PAIR_KIND)
    assert [n for n, _ in cells.tensors(tmp_path, cfg)][:2] == \
        ["pair.a", "pair.b"]
    assert model.param_count(cfg, tmp_path) == \
        9802 - (9 * 8 + 8) + (9 * 4 + 4) + (9 * 4 * 8 + 8)
    r = _run_cell(cfg=cfg, root=tmp_path)
    assert r["correct"], r["checks"]
    assert r["checks"]["compared"]["value"] > 8


def test_a_kind_with_a_wrong_reference_is_not_correct(tmp_path):
    assert DROP_SECOND_REQUANT[0] in PAIR_KIND
    cfg = _pair_root(tmp_path, PAIR_KIND.replace(*DROP_SECOND_REQUANT))
    r = _run_cell(cfg=cfg, root=tmp_path)
    assert not r["correct"]
    assert r["checks"]["logit_err"]["value"] > \
        r["checks"]["logit_err"]["limit"]
