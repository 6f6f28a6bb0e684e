"""The unified Program / Options / Executable front door (core.program).

Contracts under test:

* the deprecated shims (``plan.compile_model`` / ``plan.execute`` /
  ``LightatorDevice.run``) stay **bit-identical** to the new API and warn
  exactly once, naming the replacement;
* ``Options`` participates in the plan cache key through its *resolved*
  values: env-default and explicit-equivalent options hit the same cached
  plan, different strategies key fresh plans, and flipping the backend
  between runs re-traces the executor without recompiling the plan;
* ``Program.then`` fuses two programs into ONE compiled plan whose
  quantized output tracks the float reference of the composed IR;
* ``shard_batch`` is a graceful no-op on one device and bit-identical to
  the unsharded path on many (subprocess with forced host devices).
"""

import subprocess
import sys
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import plan as plan_mod
from repro.core.accelerator import LightatorDevice
from repro.core.program import Options, Program, infer_output_hwc
from repro.core.quant import W4A4, MX_43
from repro.imaging import PIPELINES, apply_float, psnr
from repro.kernels import dispatch
from repro.models.vision import lenet_ir, init_vision, vision_program


@pytest.fixture(scope="module")
def lenet():
    layers = tuple(lenet_ir())
    params = init_vision(jax.random.PRNGKey(0), layers)
    img = jax.random.uniform(jax.random.PRNGKey(1), (2, 28, 28, 1))
    return layers, params, img


@pytest.fixture(scope="module")
def frames():
    from repro.data.synthetic import synthetic_textures
    imgs, _ = synthetic_textures(2, hw=32, seed=0)
    return jnp.asarray(imgs)


# -- shims are bit-identical to the new API ----------------------------------

def test_shims_bit_identical_on_lenet(lenet):
    layers, params, img = lenet
    new = Program(layers, params, (28, 28, 1), name="lenet").compile(
        Options(scheme=W4A4)).run(img)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        plan = plan_mod.compile_model(layers, img.shape, W4A4)
        old_fn = plan_mod.execute(plan, params, img)
        old_dev, _ = LightatorDevice().run(layers, params, img, W4A4)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old_fn))
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old_dev))


@pytest.mark.parametrize("name", ["edge_detect", "compress_recon"])
def test_shims_bit_identical_on_imaging(frames, name):
    prog = PIPELINES[name].program(32, 32, 3)
    new = prog.compile(Options(scheme=W4A4)).run(frames)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        plan = plan_mod.compile_model(prog.layers, frames.shape, W4A4)
        old = plan_mod.execute(plan, prog.params, frames)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def test_shims_warn_once_naming_replacement(lenet):
    layers, params, img = lenet
    plan_mod._DEPRECATION_WARNED.clear()
    with pytest.warns(DeprecationWarning, match="repro.Program"):
        plan = plan_mod.compile_model(layers, img.shape, W4A4)
    with pytest.warns(DeprecationWarning, match="run\\(frames\\)"):
        plan_mod.execute(plan, params, img)
    with pytest.warns(DeprecationWarning, match="repro.Program"):
        LightatorDevice().run(layers, params, img, W4A4)
    # one-shot: a second round is silent
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        plan_mod.compile_model(layers, img.shape, W4A4)
        plan_mod.execute(plan, params, img)
        LightatorDevice().run(layers, params, img, W4A4)


# -- Options -----------------------------------------------------------------

def test_options_validation():
    with pytest.raises(ValueError, match="backend"):
        Options(backend="bogus")
    with pytest.raises(ValueError, match="conv strategy"):
        Options(conv_strategy="bogus")
    with pytest.raises(ValueError, match="fc_batch"):
        Options(fc_batch=0)
    with pytest.raises(ValueError, match="conv_vmem_budget"):
        Options(conv_vmem_budget=-1)


def test_options_resolve_env_defaults(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_CONV_STRATEGY", raising=False)
    r = Options().resolve()
    assert r.backend == dispatch.get_backend()
    assert r.conv_strategy == "auto"
    assert r.conv_vmem_budget == dispatch.conv_vmem_budget()
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    monkeypatch.setenv("REPRO_CONV_STRATEGY", "strip")
    r = Options().resolve()
    assert (r.backend, r.conv_strategy) == ("pallas", "strip")
    # explicit fields survive resolution untouched
    r = Options(backend="reference", conv_strategy="resident").resolve()
    assert (r.backend, r.conv_strategy) == ("reference", "resident")
    assert "backend=reference" in Options(backend="reference").describe()


def test_options_are_part_of_the_plan_cache_key(lenet):
    layers, params, _ = lenet
    prog = Program(layers, params, (28, 28, 1))
    base = prog.compile(Options(scheme=W4A4)).plan
    # different scheme / fc_batch / strategy / budget -> fresh plans
    assert prog.compile(Options(scheme=MX_43)).plan is not base
    assert prog.compile(Options(scheme=W4A4, fc_batch=8)).plan is not base
    assert prog.compile(Options(
        scheme=W4A4, conv_strategy="strip")).plan is not base
    assert prog.compile(Options(
        scheme=W4A4, conv_vmem_budget=1 << 16)).plan is not base
    # backend / interpret / sharding are run-time knobs, not compile keys
    assert prog.compile(Options(scheme=W4A4, backend="pallas")).plan is base
    assert prog.compile(Options(scheme=W4A4, interpret=True)).plan is base
    assert prog.compile(Options(scheme=W4A4, shard_batch=True)).plan is base


def test_env_default_and_explicit_equivalent_share_a_plan(lenet, monkeypatch):
    """Options(None) resolved from env == the same values passed explicitly:
    both must hit the SAME cached plan (resolved values key the cache)."""
    layers, params, _ = lenet
    prog = Program(layers, params, (28, 28, 1))
    monkeypatch.delenv("REPRO_CONV_STRATEGY", raising=False)
    monkeypatch.delenv("REPRO_CONV_VMEM_BUDGET", raising=False)
    p_env = prog.compile(Options(scheme=W4A4)).plan
    p_explicit = prog.compile(Options(
        scheme=W4A4, conv_strategy="auto",
        conv_vmem_budget=dispatch.DEFAULT_CONV_VMEM_BUDGET)).plan
    assert p_explicit is p_env
    # and with the env set, Options(None) follows it to the explicit twin
    monkeypatch.setenv("REPRO_CONV_STRATEGY", "strip")
    p_env_strip = prog.compile(Options(scheme=W4A4)).plan
    p_exp_strip = prog.compile(Options(scheme=W4A4,
                                       conv_strategy="strip")).plan
    assert p_env_strip is p_exp_strip
    assert p_env_strip is not p_env


def test_backend_flip_gets_a_fresh_jitted_executor(lenet):
    """Regression for the ``executor()`` keying: two Executables over the
    same plan with different backends must not share a trace — and their
    logits agree exactly (integer-exact MACs on every backend)."""
    layers, params, img = lenet
    prog = Program(layers, params, (28, 28, 1))
    e_ref = prog.compile(Options(scheme=W4A4, backend="reference"))
    e_pal = prog.compile(Options(scheme=W4A4, backend="pallas"))
    assert e_ref.plan is e_pal.plan
    out_ref = e_ref.run(img)
    with dispatch.use_backend("reference"):
        f_ref = e_ref.plan.executor()
    out_pal = e_pal.run(img)
    with dispatch.use_backend("pallas"):
        f_pal = e_pal.plan.executor()
    assert f_ref is not f_pal
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_pal))


# -- Program construction + composition --------------------------------------

def test_program_constructors():
    prog = vision_program("lenet")
    assert prog.input_hwc == (28, 28, 1) and prog.name == "lenet"
    assert prog.output_hwc == (1, 1, 10)
    assert Program.from_model("lenet").input_hwc == (28, 28, 1)
    pipe = Program.from_pipeline("edge_detect", 32, 32, 3)
    assert pipe.output_hwc == (32, 32, 1)
    with pytest.raises(ValueError, match="schedule-only"):
        vision_program("alexnet")
    with pytest.raises(ValueError, match="unknown pipeline"):
        Program.from_pipeline("bogus", 32, 32)
    with pytest.raises(ValueError, match="input_hwc"):
        Program((), {}, (32, 32))


def test_infer_output_hwc_matches_compiled_shapes(frames):
    """infer_output_hwc must stay in lockstep with the compile pass's own
    shape walk (it is a scheduling-free copy of the same arithmetic)."""
    for name in ("edge_detect", "denoise_box", "compress_recon",
                 "compress_recon_deconv", "sharpen"):
        prog = PIPELINES[name].program(32, 32, 3)
        out = prog.compile(Options(scheme=W4A4)).run(frames)
        assert tuple(out.shape[1:]) == infer_output_hwc(prog.layers,
                                                        prog.input_hwc)
    # vision models: the plan's own out_features vs the inferred channel dim
    for model in ("lenet", "vgg9", "vgg16"):
        prog = vision_program(model, params={})
        plan = prog.compile(Options(scheme=W4A4)).plan
        assert infer_output_hwc(prog.layers, prog.input_hwc) == \
            (1, 1, plan.out_features)


def test_then_rejects_shape_mismatch():
    den = Program.from_pipeline("denoise_box", 32, 32, 3)
    edge16 = Program.from_pipeline("edge_detect", 16, 16, 3)
    with pytest.raises(ValueError, match="cannot chain"):
        den.then(edge16)


def test_then_chain_compiles_as_one_plan(frames):
    """Acceptance: denoise -> edge chains into a single CompiledPlan, runs
    batch-first, and the quantized output tracks the float reference of the
    composed IR within the existing per-pipeline PSNR floors."""
    chain = (Program.from_pipeline("denoise_box", 32, 32, 3)
             .then(Program.from_pipeline("edge_detect", 32, 32, 3)))
    assert chain.name == "denoise_box>edge_detect"
    exe = chain.compile(Options(scheme=W4A4))
    assert isinstance(exe.plan, plan_mod.CompiledPlan)
    # one plan holds BOTH stages' schedules (box dw conv + CA + grad + mag)
    assert len(exe.plan.schedules) == 4
    out = exe.run(frames)
    assert out.shape == (frames.shape[0], 32, 32, 1)     # batch-first
    ref = apply_float(chain.layers, chain.params, frames)
    p = float(psnr(ref, out))
    floor = 20.0          # the edge_detect floor (test_imaging.PSNR_FLOORS)
    assert p > floor, f"chain PSNR {p:.2f} dB under floor {floor}"
    # float composition of the two stages == float of the fused program
    den = Program.from_pipeline("denoise_box", 32, 32, 3)
    edge = Program.from_pipeline("edge_detect", 32, 32, 3)
    staged = apply_float(edge.layers, edge.params,
                         apply_float(den.layers, den.params, frames))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(staged),
                               rtol=1e-5, atol=1e-6)


def test_then_three_stage_chain_runs(frames):
    """compress -> recon -> sharpen: a recon pipeline feeding a filter."""
    chain = (Program.from_pipeline("compress_recon", 32, 32, 3)
             .then(Program.from_pipeline("sharpen", 32, 32, 1)))
    out = chain.compile(Options(scheme=W4A4)).run(frames)
    assert out.shape == (frames.shape[0], 32, 32, 1)
    ref = apply_float(chain.layers, chain.params, frames)
    assert float(psnr(ref, out)) > 10.0   # sharpen-family floor


def test_then_renames_colliding_layers(frames):
    """Chaining two instances of the same pipeline suffixes the repeated
    layer names in IR and params consistently."""
    e3 = Program.from_pipeline("edge_detect", 32, 32, 3)
    e1 = Program.from_pipeline("edge_detect", 32, 32, 1)
    twice = e3.then(e1)
    names = [l.name for l in twice.layers if hasattr(l, "name")]
    assert names == ["grad", "edge_mag", "grad.2", "edge_mag.2"]
    assert set(names) <= set(twice.params)
    out = twice.compile(Options(scheme=W4A4)).run(frames)
    assert out.shape == (frames.shape[0], 32, 32, 1)


def test_report_mutation_does_not_corrupt_shared_plan(lenet):
    """Executable.report is a private copy: the plan is shared through the
    global cache, so caller mutations must stay local."""
    layers, params, _ = lenet
    prog = Program(layers, params, (28, 28, 1))
    e1 = prog.compile(Options(scheme=W4A4))
    e2 = prog.compile(Options(scheme=W4A4))
    assert e1.plan is e2.plan
    true_fps = e1.plan.report.fps
    e1.report.fps = -1.0
    assert e1.report.fps == -1.0            # the copy sticks per Executable
    assert e2.report.fps == true_fps        # ...without leaking across
    assert e1.plan.report.fps == true_fps   # ...or into the cached plan


# -- batch sharding ----------------------------------------------------------

def test_shard_batch_noop_on_single_device(lenet):
    """On one device (or a non-dividing batch) sharding must change nothing
    — same logits, same code path."""
    layers, params, img = lenet
    prog = Program(layers, params, (28, 28, 1))
    base = prog.compile(Options(scheme=W4A4)).run(img)
    sharded = prog.compile(Options(scheme=W4A4, shard_batch=True)).run(img)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(sharded))


_SHARD_SCRIPT = """
import dataclasses
import jax, numpy as np
import repro
from repro.core.quant import W4A4
assert len(jax.local_devices()) == 4, jax.local_devices()
prog = repro.Program.from_model("lenet")
frames = jax.random.uniform(jax.random.PRNGKey(1), (8, 28, 28, 1))
base = prog.compile(repro.Options(scheme=W4A4)).run(frames)
exe = prog.compile(repro.Options(scheme=W4A4, shard_batch=True))
out = exe.run(frames)
assert "batch" in str(out.sharding), out.sharding
np.testing.assert_array_equal(np.asarray(base), np.asarray(out))
# ragged batch (5 % 4 != 0): graceful no-op, still correct
np.testing.assert_array_equal(
    np.asarray(exe.run(frames[:5])),
    np.asarray(prog.compile(repro.Options(scheme=W4A4)).run(frames[:5])))
# an explicit mesh with a caller-chosen axis name shards too
mesh = jax.sharding.Mesh(np.asarray(jax.local_devices()), ("data",))
out = prog.compile(repro.Options(scheme=W4A4, shard_batch=True,
                                 mesh=mesh)).run(frames)
assert "data" in str(out.sharding), out.sharding
np.testing.assert_array_equal(np.asarray(base), np.asarray(out))
# Pallas kernels cannot be partitioned by the compiler: each shard runs
# them on its own frames (shard_map), per-frame serving semantics
opts = repro.Options(scheme=W4A4, backend="pallas", interpret=True)
base = prog.compile(opts).run_per_frame(frames)
out = prog.compile(dataclasses.replace(opts, shard_batch=True)) \
    .run_per_frame(frames)
assert len(out.sharding.device_set) == 4, out.sharding
np.testing.assert_array_equal(np.asarray(base), np.asarray(out))
print("SHARD_OK")
"""


def test_shard_batch_multi_device_bit_identical():
    """ROADMAP item: the batch axis shards over a mesh via NamedSharding.
    Forced 4-way host platform in a subprocess (device count is fixed at
    jax init); sharded logits must equal the single-device ones exactly."""
    import os
    env = dict(os.environ,
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"),
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    res = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                         cwd=Path(__file__).resolve().parent.parent,
                         env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "SHARD_OK" in res.stdout
