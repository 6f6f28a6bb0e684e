"""LightatorDevice — the paper's "custom in-house simulator" (Sec. 5).

The device models a vision model the way the hardware runs it:

  step 1  frame captured; CRC quantizes pixels to uint4 (ADC-less imager)
  step 2  optional Compressive Acquisitor (fused RGB->gray + pooling)
  step 3  All-in-One Convolver runs the layer's MACs on the OC banks
  step 4  electronic activation (Sign/ReLU/tanh) + CRC requantization feeds
          the DMVA for the next layer (activation banks eliminated)
  step 5  repeat 3<->4 until the classifier output

Execution is split into two passes (``core.plan``):

  * **compile** — ``plan.compile_model`` resolves per-layer [W:A] specs, OC
    schedules, and the power/latency report once from shapes, and caches the
    resulting ``CompiledPlan`` per (layers, scheme, input shape, hardware).
  * **execute** — ``plan.execute`` runs the integer-exact quantized numerics
    end-to-end under a single ``jax.jit``, batch-first, with the MAC work
    routed through the Pallas kernels via ``kernels.dispatch``.

``LightatorDevice.run`` is a thin wrapper over the two passes and keeps the
seed signature: it returns (logits, report). The original eager per-layer
interpreter survives as ``run_eager`` — the executable specification that
the compiled path must match bit-for-bit (see tests/test_plan_compile.py).

The model is described by a small layer IR (``ConvSpec``/``DenseSpec``/...)
emitted by ``models.vision``; weights are plain pytrees from QAT training.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import optical_core as ocore
from repro.core import power_model as pmod
from repro.core.compressive import compressive_acquire, window_pool
from repro.core.quant import (WASpec, MixedPrecisionScheme, ACT_BITS,
                              quantize_weight, resolve_layer_specs)
from repro.kernels import dispatch


# ---------------------------------------------------------------------------
# Layer IR
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CASpec:
    pool: int = 2
    rgb_to_gray: bool = True


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    name: str
    c_in: int
    c_out: int
    kernel: int = 3
    stride: int = 1
    padding: str = "SAME"
    act: str = "relu"               # relu | sign | tanh | abs | none
    pool: Optional[Tuple[str, int]] = None   # ("avg"|"max", size)
    # Depthwise filtering: the same (or a per-channel) k x k filter applied to
    # each input channel independently (c_out == c_in, weights [k,k,1,c]).
    # This is how the imaging pipelines run fixed-function filters over RGB
    # frames without collapsing channels — each channel is one single-channel
    # conv on the OC banks (k*k taps per arm group, c_out strides).
    depthwise: bool = False


@dataclasses.dataclass(frozen=True)
class UpsampleSpec:
    """Reconstruction upsample (the CA's inverse for compress->recon).

    Runs as preset-weight interpolation banks: every output pixel is a fixed
    weighted sum of <= 4 neighbouring inputs (bilinear) or a copy (nearest) —
    the same preset-MAC structure as the CA, so it is scheduled like a CA
    layer (no DACs, no remaps).
    """

    factor: int = 2
    method: str = "bilinear"        # bilinear | nearest


@dataclasses.dataclass(frozen=True)
class DenseSpec:
    name: str
    fan_in: int
    fan_out: int
    act: str = "relu"


@dataclasses.dataclass(frozen=True)
class FlattenSpec:
    pass


LayerIR = CASpec | ConvSpec | DenseSpec | FlattenSpec | UpsampleSpec


def _activation(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "relu":
        return jax.nn.relu(x)
    if kind == "sign":
        return jnp.sign(x)
    if kind == "tanh":
        return jnp.tanh(x)
    if kind == "abs":
        # magnitude readout: the BPD's two rails measured without sign —
        # what edge-magnitude pipelines consume
        return jnp.abs(x)
    if kind == "none":
        return x
    raise ValueError(f"unknown activation {kind}")


def _crc_requant(x: jnp.ndarray, a_bits: int = ACT_BITS):
    """Electronic output -> CRC codes for the next layer's DMVA.

    Returns (codes uint, scale). Unsigned: activations are light intensity.
    Scale calibrated per-tensor to the observed max (the reference-voltage
    ladder spans the pixel/previous-layer output range).
    """
    qmax = (1 << a_bits) - 1
    x = jnp.maximum(x, 0.0)
    scale = jnp.maximum(jnp.max(x), 1e-8) / qmax
    codes = jnp.clip(jnp.round(x / scale), 0, qmax)
    return codes, scale


class LightatorDevice:
    """Execute a layer-IR model with photonic quantized semantics + report."""

    def __init__(self, oc: ocore.OCConfig = ocore.DEFAULT_OC,
                 circuit: pmod.CircuitConstants = pmod.DEFAULT_CIRCUIT,
                 profile: pmod.AcceleratorProfile = pmod.LIGHTATOR_PROFILE):
        self.oc = oc
        self.power = pmod.PowerModel(oc, circuit, profile)

    # -- numerics ---------------------------------------------------------
    def _conv(self, codes: jnp.ndarray, act_scale: jnp.ndarray,
              w: jnp.ndarray, b: jnp.ndarray | None, spec: ConvSpec,
              wa: WASpec) -> jnp.ndarray:
        """Integer-exact quantized conv. codes: [B,H,W,Cin] uint codes."""
        wq, ws = quantize_weight(w, wa, axis=-1)   # w: [k,k,cin,cout]
        acc = jax.lax.conv_general_dilated(
            codes.astype(jnp.float32), wq.astype(jnp.float32),
            window_strides=(spec.stride, spec.stride), padding=spec.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        out = acc * (act_scale * ws.reshape(1, 1, 1, -1))
        if b is not None:
            out = out + b
        return out

    def _dense(self, codes: jnp.ndarray, act_scale: jnp.ndarray,
               w: jnp.ndarray, b: jnp.ndarray | None, wa: WASpec):
        wq, ws = quantize_weight(w, wa, axis=-1)
        acc = codes.astype(jnp.float32) @ wq.astype(jnp.float32)
        out = acc * (act_scale * ws.reshape(1, -1))
        if b is not None:
            out = out + b
        return out

    # -- the device -------------------------------------------------------
    def compile(self, layers: Sequence[LayerIR],
                input_shape: Tuple[int, ...],
                scheme: WASpec | MixedPrecisionScheme):
        """Static pass: layers + input shape -> cached ``CompiledPlan``."""
        from repro.core import plan as plan_mod
        return plan_mod._compile_model(
            tuple(layers), tuple(input_shape), scheme, oc=self.oc,
            circuit=self.power.c, profile=self.power.profile,
            weight_sram_kb=self.power.weight_sram_kb,
            act_sram_kb=self.power.act_sram_kb)

    def run(self, layers: Sequence[LayerIR], params: Dict[str, Dict],
            image: jnp.ndarray,
            scheme: WASpec | MixedPrecisionScheme) -> Tuple[jnp.ndarray, pmod.ModelReport]:
        """image: [B,H,W,C] float in [0,1]. Returns (logits, report).

        Deprecated compatibility wrapper (cached compile + jitted batched
        execute, bit-identical to ``run_eager``) — the front door is now
        ``repro.Program(layers, params, hwc).compile(Options(...))``, which
        also exposes the report without recomputation.
        """
        import copy

        from repro.core import plan as plan_mod
        plan_mod._warn_deprecated(
            "LightatorDevice.run",
            "repro.Program(layers, params, input_hwc)"
            ".compile(repro.Options(scheme=...)).run(image)")
        plan = self.compile(layers, image.shape, scheme)
        logits = plan_mod._execute(plan, params, image)
        # deep copy: the plan (and its report) is shared via the global plan
        # cache; callers mutating their report must not corrupt future runs
        return logits, copy.deepcopy(plan.report)

    def run_eager(self, layers: Sequence[LayerIR], params: Dict[str, Dict],
                  image: jnp.ndarray,
                  scheme: WASpec | MixedPrecisionScheme) -> Tuple[jnp.ndarray, pmod.ModelReport]:
        """The seed per-layer eager interpreter (reference semantics).

        Re-schedules and re-runs the power model on every call; kept as the
        specification the compiled path is regression-tested against, and as
        the baseline for ``benchmarks.bench_pipeline``.

        Covers the seed IR only: the imaging extensions (depthwise convs,
        ``UpsampleSpec``) execute exclusively on the compiled path — their
        quality oracle is the float reference (``imaging.apply_float``), not
        this interpreter — and are rejected here with a clear error.
        """
        compute_layers = [l for l in layers
                          if isinstance(l, (ConvSpec, DenseSpec))]
        specs = resolve_layer_specs(len(compute_layers), scheme)
        spec_iter = iter(specs)

        schedules: List[ocore.OCSchedule] = []
        spec_list: List[WASpec] = []
        conv_strategy: Dict[str, Dict] = {}
        # chain geoms aligned with the plan's step indices (each seed-IR
        # layer compiles to exactly one step), so the fused-segment report
        # resolves identically to the compile pass
        geoms: List[Optional[dispatch.ChainGeom]] = []

        # step 1: ADC-less imager — CRC on raw pixels
        codes, act_scale = _crc_requant(image)
        x = codes

        for layer in layers:
            if isinstance(layer, CASpec):
                # step 2: compressive acquisition on *dequantized* intensities
                intens = x * act_scale
                g = compressive_acquire(intens, layer.pool, layer.rgb_to_gray)
                if g.ndim == 3:
                    g = g[..., None]
                h, w_ = g.shape[1:3]
                schedules.append(ocore.schedule_ca(
                    "CA", h, w_, layer.pool,
                    channels=image.shape[-1], oc=self.oc))
                spec_list.append(WASpec(4, 4))
                geoms.append(None)
                x, act_scale = _crc_requant(g)
            elif isinstance(layer, ConvSpec):
                if layer.depthwise:
                    raise NotImplementedError(
                        f"{layer.name}: depthwise convs run on the compiled "
                        f"path only (core.plan.execute); the eager "
                        f"interpreter covers the seed IR")
                wa = next(spec_iter)
                p = params[layer.name]
                pads = jax.lax.padtype_to_pads(
                    (x.shape[1], x.shape[2]), (layer.kernel, layer.kernel),
                    (layer.stride, layer.stride), layer.padding)
                geoms.append(dispatch.ChainGeom(
                    layer.name, x.shape[1], x.shape[2], layer.c_in,
                    layer.c_out, layer.kernel, layer.stride,
                    tuple((int(lo), int(hi)) for lo, hi in pads),
                    act=layer.act, pool=layer.pool))
                y = self._conv(x, act_scale, p["w"], p.get("b"), layer, wa)
                # record the conv strategy the kernel path would choose for
                # this layer's (pre-pool) output dims — same resolution as
                # the compile pass, so reports stay field-for-field equal
                conv_strategy[layer.name] = dataclasses.asdict(
                    dispatch.select_conv_strategy(
                        y.shape[1], y.shape[2], layer.c_in, layer.c_out,
                        layer.kernel, layer.stride))
                y = _activation(y, layer.act)
                if layer.pool is not None:
                    kind, size = layer.pool
                    y = window_pool(y, kind, size)
                    if kind == "avg":
                        # avg pooling runs on CA banks with pre-set weights
                        schedules.append(ocore.schedule_ca(
                            f"{layer.name}.pool", y.shape[1], y.shape[2],
                            size, channels=1, oc=self.oc))
                        spec_list.append(WASpec(4, 4))
                h_out, w_out = y.shape[1:3]
                schedules.append(ocore.schedule_conv(
                    layer.name, h_out, w_out, layer.c_in, layer.c_out,
                    layer.kernel, oc=self.oc))
                spec_list.append(wa)
                x, act_scale = _crc_requant(y)        # step 4: DMVA reuse
            elif isinstance(layer, FlattenSpec):
                intens = x * act_scale
                flat = intens.reshape(intens.shape[0], -1)
                geoms.append(None)
                x, act_scale = _crc_requant(flat)
            elif isinstance(layer, DenseSpec):
                geoms.append(None)
                wa = next(spec_iter)
                p = params[layer.name]
                y = self._dense(x, act_scale, p["w"], p.get("b"), wa)
                schedules.append(ocore.schedule_fc(
                    layer.name, layer.fan_in, layer.fan_out,
                    batch=1, oc=self.oc))
                spec_list.append(wa)
                if layer.act != "none":
                    y = _activation(y, layer.act)
                    x, act_scale = _crc_requant(y)
                else:
                    # classifier head: logits leave the device (transmitter)
                    x, act_scale = y, jnp.asarray(1.0)
            else:
                raise TypeError(f"unknown layer IR {layer!r}")

        logits = x * act_scale if act_scale.ndim == 0 else x
        # architecture report with the per-layer specs actually used
        lps = [self.power.layer_power(pmod.LayerSchedule(s, sp))
               for s, sp in zip(schedules, spec_list)]
        report = self.power.finalize_report(lps, schedules, scheme)
        report.conv_strategy = conv_strategy
        report.fused_segments = [
            dataclasses.asdict(f)
            for f in dispatch.select_fused_segments(geoms)]
        return logits, report
