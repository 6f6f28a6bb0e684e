"""repro.imaging — golden tests for the fixed-function pipelines.

Float path: analytic expectations (classical filter identities) on
deterministic synthetic frames. Quantized path: every pipeline compiled via
core.plan under [4:4] must stay within a per-pipeline PSNR floor of the
float reference — the device's 4-bit CRC + MR quantization budget.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.accelerator import ConvSpec, UpsampleSpec
from repro.core.quant import W4A4
from repro.imaging import (PIPELINES, apply_float, fit_recon_head,
                           gray_target, psnr, ssim,
                           recon_head_identity_params)
from repro.kernels import dispatch

HW = 32


@pytest.fixture(scope="module")
def frames():
    from repro.data.synthetic import synthetic_textures
    imgs, _ = synthetic_textures(2, hw=HW, seed=0)
    return jnp.asarray(imgs)


def _const_rgb(val=0.5, hw=HW):
    return jnp.full((1, hw, hw, 3), val, jnp.float32)


# -- float-path golden identities -------------------------------------------

def test_edge_detect_zero_on_constant():
    layers, params = PIPELINES["edge_detect"].build(HW, HW, 3)
    out = apply_float(layers, params, _const_rgb())
    # gradient of a constant is zero away from the border padding
    np.testing.assert_allclose(np.asarray(out[:, 2:-2, 2:-2]), 0.0,
                               atol=1e-5)


@pytest.mark.parametrize("name", ["edge_detect", "prewitt_edge"])
def test_edge_detect_peaks_on_step(name):
    img = jnp.zeros((1, HW, HW, 3)).at[:, :, HW // 2:, :].set(1.0)
    layers, params = PIPELINES[name].build(HW, HW, 3)
    out = apply_float(layers, params, img)[0, :, :, 0]
    # response is maximal on the two columns adjacent to the vertical step
    # and zero in the flat regions (away from the zero-padded border, which
    # itself reads as an edge of the bright half)
    peak = np.asarray(out[2:-2, HW // 2 - 1: HW // 2 + 1])
    assert peak.min() > 1.0
    np.testing.assert_allclose(np.asarray(out[2:-2, 2:HW // 2 - 2]), 0.0,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[2:-2, HW // 2 + 2:-2]), 0.0,
                               atol=1e-5)


@pytest.mark.parametrize("name", ["sharpen", "unsharp_mask"])
def test_sharpen_preserves_constant(name):
    """Sharpening kernels sum to 1: flat regions pass through unchanged."""
    layers, params = PIPELINES[name].build(HW, HW, 3)
    out = apply_float(layers, params, _const_rgb(0.4))
    gray = float(gray_target(_const_rgb(0.4))[0, HW // 2, HW // 2, 0])
    margin = 3                       # outside border-padding influence
    np.testing.assert_allclose(
        np.asarray(out[:, margin:-margin, margin:-margin, 0]), gray,
        rtol=1e-5)


def test_denoise_impulse_response():
    """A unit impulse spreads to exactly the kernel coefficients."""
    from repro.imaging.filters import gaussian_kernel
    img = jnp.zeros((1, HW, HW, 3)).at[0, HW // 2, HW // 2, 1].set(1.0)
    layers, params = PIPELINES["denoise_gauss"].build(HW, HW, 3)
    out = apply_float(layers, params, img)
    k = gaussian_kernel(5, 1.0)
    got = np.asarray(out[0, HW // 2 - 2:HW // 2 + 3,
                         HW // 2 - 2:HW // 2 + 3, 1])
    np.testing.assert_allclose(got, k, rtol=1e-5)
    # untouched channels stay zero (depthwise: no cross-channel mixing)
    np.testing.assert_allclose(np.asarray(out[..., 0]), 0.0, atol=1e-7)
    np.testing.assert_allclose(np.asarray(out[..., 2]), 0.0, atol=1e-7)


def test_compress_recon_constant_roundtrip():
    layers, params = PIPELINES["compress_recon"].build(HW, HW, 3)
    out = apply_float(layers, params, _const_rgb(0.6))
    gray = float(gray_target(_const_rgb(0.6))[0, 0, 0, 0])
    np.testing.assert_allclose(np.asarray(out), gray, rtol=1e-5)


def test_deconv_head_identity_at_init(frames):
    """Identity-initialized head == plain bilinear reconstruction."""
    l_bi, p_bi = PIPELINES["compress_recon"].build(HW, HW, 3)
    l_dc, p_dc = PIPELINES["compress_recon_deconv"].build(HW, HW, 3)
    np.testing.assert_allclose(np.asarray(apply_float(l_dc, p_dc, frames)),
                               np.asarray(apply_float(l_bi, p_bi, frames)),
                               atol=1e-6)


def test_fit_recon_head_improves_psnr(frames):
    layers, params = PIPELINES["compress_recon_deconv"].build(HW, HW, 3)
    tgt = gray_target(frames)
    before = float(psnr(tgt, apply_float(layers, params, frames)))
    fitted = fit_recon_head(layers, params, frames, steps=60)
    after = float(psnr(tgt, apply_float(layers, fitted, frames)))
    assert after > before


# -- quantized device path vs float reference --------------------------------

# Per-pipeline PSNR floors (dB) for [4:4] on 32x32 textures: the device's
# 4-bit activation budget. The sharpen family sits lowest because its
# outputs overshoot negative and the CRC's non-negativity clamp (absent
# from the float oracle) adds clipping error on top of quantization.
PSNR_FLOORS = {
    "edge_detect": 20.0, "prewitt_edge": 20.0,
    "sharpen": 10.0, "unsharp_mask": 10.0,
    "denoise_gauss": 20.0, "denoise_box": 24.0,
    "compress_recon": 24.0, "compress_recon_deconv": 24.0,
}


@pytest.mark.parametrize("name", sorted(PIPELINES))
def test_quantized_tracks_float(frames, name):
    exe = PIPELINES[name].program(HW, HW, 3).compile(
        repro.Options(scheme=W4A4))
    out = exe.run(frames)
    ref = apply_float(exe.program.layers, exe.program.params, frames)
    assert out.shape == ref.shape
    p = float(psnr(ref, out))
    assert p > PSNR_FLOORS[name], f"{name}: PSNR {p:.2f} dB under floor"
    assert float(ssim(ref, out)) > 0.5
    # image-valued plans report spatial outputs, power report is populated
    assert out.ndim == 4 and exe.report.fps > 0


def test_registry_entries_consistent():
    for name, pipe in PIPELINES.items():
        assert pipe.name == name
        assert pipe.kind in ("filter", "recon")
        with pytest.raises(ValueError, match="channels"):
            pipe.build(HW, HW, 2)


def test_pipelines_accept_grayscale_input(frames):
    gray = gray_target(frames)
    for name in ("edge_detect", "denoise_box", "compress_recon"):
        prog = PIPELINES[name].program(HW, HW, 1)
        out = prog.compile(repro.Options(scheme=W4A4)).run(gray)
        ref = apply_float(prog.layers, prog.params, gray)
        assert out.shape == ref.shape
        assert float(psnr(ref, out)) > 15.0


# -- plan-runtime growth: depthwise conv + upsample step ---------------------

@pytest.mark.parametrize("stride", [1, 2])
def test_depthwise_conv_int_matches_manual(stride):
    key = jax.random.PRNGKey(0)
    codes = jnp.round(jax.random.uniform(key, (2, 8, 8, 3)) * 15)
    wq = jnp.round(jax.random.uniform(jax.random.PRNGKey(1),
                                      (3, 3, 1, 3)) * 14) - 7
    pads = ((1, 1), (1, 1))
    out = dispatch.conv_int(codes, wq, stride, pads, groups=3)
    assert out.shape == (2, 8 // stride, 8 // stride, 3)
    for ch in range(3):
        ref = jax.lax.conv_general_dilated(
            codes[..., ch:ch + 1], wq[..., ch:ch + 1], (stride, stride), pads,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        np.testing.assert_array_equal(np.asarray(out[..., ch]),
                                      np.asarray(ref[..., 0]))


def test_depthwise_conv_int_backends_agree():
    codes = jnp.round(jax.random.uniform(jax.random.PRNGKey(2),
                                         (1, 8, 8, 3)) * 15)
    wq = jnp.round(jax.random.uniform(jax.random.PRNGKey(3),
                                      (3, 3, 1, 3)) * 14) - 7
    pads = ((1, 1), (1, 1))
    with dispatch.use_backend("reference"):
        ref = dispatch.conv_int(codes, wq, 1, pads, groups=3)
    with dispatch.use_backend("pallas"):
        pal = dispatch.conv_int(codes, wq, 1, pads, groups=3)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))


def test_depthwise_requires_matching_channels():
    layers = (ConvSpec("dw", 3, 4, kernel=3, depthwise=True),)
    with pytest.raises(ValueError, match="depthwise"):
        repro.Program(layers, {}, (8, 8, 3)).compile()


def test_upsample_step_shapes_and_schedule():
    from repro.core.compressive import upsample_reconstruct
    layers = (UpsampleSpec(factor=2, method="bilinear"),)
    exe = repro.Program(layers, {}, (8, 8, 1)).compile(
        repro.Options(scheme=W4A4))
    assert exe.plan.schedules[-1].kind == "ca"      # preset banks, no remaps
    assert exe.plan.schedules[-1].weight_remaps == 0
    x = jax.random.uniform(jax.random.PRNGKey(4), (1, 8, 8, 1))
    out = exe.run(x)
    assert out.shape == (1, 16, 16, 1)
    # quantization aside, the step is the shared upsample_reconstruct
    ref = upsample_reconstruct(x, 2, "bilinear")
    assert float(psnr(ref, out)) > 25.0
    with pytest.raises(ValueError, match="method"):
        repro.Program((UpsampleSpec(2, "bicubic"),), {}, (8, 8, 1)).compile()
    # multi-channel upsample: windows (and the report's cycle count) scale
    # with C — each channel interpolates independently on the preset banks
    e3 = repro.Program(layers, {}, (8, 8, 3)).compile(
        repro.Options(scheme=W4A4))
    assert e3.plan.schedules[-1].cycles == 3 * exe.plan.schedules[-1].cycles
    out3 = e3.run(jax.random.uniform(jax.random.PRNGKey(5), (1, 8, 8, 3)))
    assert out3.shape == (1, 16, 16, 3)


def test_conv_int_rejects_bad_groups():
    codes = jnp.zeros((1, 4, 4, 3))
    wq = jnp.zeros((3, 3, 1, 4))
    with pytest.raises(ValueError, match="groups"):
        dispatch.conv_int(codes, wq, 1, ((1, 1), (1, 1)), groups=3)


def test_run_eager_rejects_imaging_ir(frames):
    """The eager interpreter covers the seed IR only; imaging runs compiled."""
    from repro.core.accelerator import LightatorDevice
    dev = LightatorDevice()
    layers, params = PIPELINES["denoise_box"].build(HW, HW, 3)
    with pytest.raises(NotImplementedError, match="depthwise"):
        dev.run_eager(layers, params, frames, W4A4)
    layers, params = PIPELINES["compress_recon"].build(HW, HW, 3)
    with pytest.raises(TypeError, match="unknown layer IR"):
        dev.run_eager(layers, params, frames, W4A4)


# -- serving smoke -----------------------------------------------------------

@pytest.mark.parametrize("wait_ms", ["0", "2"])
def test_serve_vision_pipeline_smoke(wait_ms):
    """The acceptance-criteria entry point, tiny: immediate-dispatch +
    micro-batched collection through the repro.serve runtime."""
    from repro.launch import serve_vision
    fps = serve_vision.main(["--pipeline", "edge_detect", "--batch", "2",
                             "--batches", "2", "--size", "16",
                             "--max-wait-ms", wait_ms])
    assert fps > 0
