"""Static compile pass + jitted execute pass for the Lightator device.

The seed ``LightatorDevice.run`` was an eager per-frame Python interpreter:
every call re-resolved [W:A] specs, rebuilt OC schedules, re-ran the power
model, and dispatched each layer's math as separate un-jitted XLA calls.
All of that scheduling work is data-independent — it depends only on the
layer IR, the [W:A] scheme, and the input shape. This module splits it out:

  compile_model(layers, input_shape, scheme, ...) -> CompiledPlan
      Runs shape inference over the IR once, resolves per-layer ``WASpec``s,
      builds every ``OCSchedule`` and the full power/latency ``ModelReport``,
      and precomputes the static geometry (conv pads, strides, output dims)
      the execute pass needs. Plans are cached on
      ``(layers, input_shape, scheme, oc, circuit, profile, sram)`` so a
      serving loop compiles exactly once per model/shape.

  execute(plan, params, frames) -> logits
      A pure function of (params, frames), jitted once per plan, batch-first.
      It reproduces the eager interpreter's integer-exact quantized numerics
      bit-for-bit, but routes the MAC work through the kernel dispatch layer
      (``kernels.dispatch``): on the pallas backend convs go via im2col into
      the photonic MVM kernel and the CA through the fused ca_pool kernel;
      the reference backend uses the integer-exact jnp/lax oracles (convs
      stay ``conv_general_dilated`` — no patch materialization on large
      frames; depthwise convs a tap loop). Because the OC accumulate is exact integer arithmetic on
      both backends, conv/dense routing cannot change the logits; with the
      dequant/activation/requant expressions kept textually identical to
      preserve float associativity, the compiled path is bit-identical to
      the seed eager path. One carve-out: the CA stage is *float* math, and
      the fused ca_pool kernel's summation order differs from the reference
      einsum by ~1 ulp — so on the pallas backend, CA-bearing models are
      bit-identical only up to CRC requant absorbing that ulp (models
      without a CASpec, like LeNet, stay exactly bit-identical on every
      backend; everything is exact on the reference backend).

``LightatorDevice.run`` is now a thin compatibility wrapper over these two
passes; ``launch.serve_vision`` streams frame batches through a compiled
plan and reports measured frames/s next to the model's simulated FPS/W.

The public front door over both passes is ``repro.core.program``:
``Program.compile(Options) -> Executable`` — ``compile_model`` / ``execute``
remain as deprecated bit-identical shims (see docs/api.md).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import optical_core as ocore
from repro.core import power_model as pmod
from repro.core.quant import (ACT_BITS, WASpec, MixedPrecisionScheme,
                              resolve_layer_specs)
from repro.kernels import dispatch


# ---------------------------------------------------------------------------
# Bit-identity helpers
#
# The eager interpreter runs op-by-op: every scalar literal is staged as an
# executable *parameter* and every mul/add is its own XLA computation. Under
# one fused jax.jit, XLA inlines literals (rewriting x/15 into x * (1/15),
# off by 1 ULP) and LLVM contracts mul+add chains into FMAs. Both break
# bit-identity with the eager reference, and neither optimization_barrier
# nor the XLA fast-math flags stop them. So:
#
#   * quantization divisors (CRC a_qmax, MR w_qmax) are passed into the
#     jitted executor as *traced* scalars — divisions by a parameter are
#     never rewritten, exactly like the eager path's weak-typed literals;
#   * `_nofma` (nextafter(x, x), an exact identity XLA expands to integer
#     bit-ops) is inserted between the dequant multiply and the bias add,
#     so LLVM never sees a contractible fmul->fadd edge.
# ---------------------------------------------------------------------------

def _nofma(x: jnp.ndarray) -> jnp.ndarray:
    """Exact identity that blocks FMA contraction of producer*... + b."""
    return jnp.nextafter(x, x)


def _crc_requant_traced(x: jnp.ndarray, a_qmax: jnp.ndarray,
                        per_frame: bool = False,
                        axis_name: Optional[str] = None):
    """`accelerator._crc_requant` with the divisor as a traced scalar.

    ``per_frame=False`` is the seed semantics: ONE scale from a max over the
    whole tensor, batch axis included — a frame's codes depend on the other
    frames in its batch. ``per_frame=True`` reduces the max over each
    frame's own axes instead (scale shape [B, 1, ...]), the hardware's
    frame-per-pass calibration: every frame's numerics become independent
    of batch composition, which is what lets the serving micro-batcher
    coalesce and pad requests without perturbing anyone's results. At
    batch 1 the two modes are the same reduction — bit-identical.

    ``axis_name`` names the mesh axis the batch is split over when the
    executor runs per shard (``shard_map``): the per-tensor max then also
    reduces across shards (``pmax`` — exact, like any max).
    """
    x = jnp.maximum(x, 0.0)
    if per_frame:
        axes = tuple(range(1, x.ndim))
        amax = jnp.max(x, axis=axes, keepdims=True)
    else:
        amax = jnp.max(x)
        if axis_name is not None:
            amax = jax.lax.pmax(amax, axis_name)
    scale = jnp.maximum(amax, 1e-8) / a_qmax
    codes = jnp.clip(jnp.round(x / scale), 0, (1 << ACT_BITS) - 1)
    return codes, scale


def _quantize_weight_traced(w: jnp.ndarray, spec: WASpec,
                            w_qmax: jnp.ndarray):
    """`quant.quantize_weight(axis=-1)` with the divisor as a traced scalar."""
    reduce_axes = tuple(range(w.ndim - 1))
    if spec.per_channel:
        amax = jnp.max(jnp.abs(w), axis=reduce_axes, keepdims=True)
    else:
        amax = jnp.max(jnp.abs(w))
    s = jnp.maximum(amax, 1e-8) / w_qmax
    q = jnp.clip(jnp.round(w / s), -spec.w_qmax, spec.w_qmax).astype(jnp.int8)
    return q, s


# ---------------------------------------------------------------------------
# Shape inference helpers (shared with models.vision.vision_schedules)
# ---------------------------------------------------------------------------

def conv_out_hw(hw: int, kernel: int, stride: int, padding: str) -> int:
    """Spatial output size of a conv, matching XLA's SAME/VALID semantics."""
    if padding == "VALID":
        return (hw - kernel) // stride + 1
    return -(-hw // stride)                      # SAME: ceil(hw / stride)


# ---------------------------------------------------------------------------
# Plan steps: the IR annotated with everything shape-derived
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CAStep:
    pool: int
    rgb_to_gray: bool


@dataclasses.dataclass(frozen=True)
class ConvStep:
    name: str
    wa: WASpec
    kernel: int
    stride: int
    act: str
    pool: Optional[Tuple[str, int]]
    pads: Tuple[Tuple[int, int], Tuple[int, int]]   # ((lo,hi) per spatial dim)
    groups: int = 1                 # feature groups (c_in for depthwise)
    # conv execution strategy (resident vs strip-mined + strip geometry),
    # resolved once at compile time from the layer's output dims and the
    # REPRO_CONV_STRATEGY / VMEM-budget environment (kernels.dispatch)
    strategy: Optional[dispatch.ConvStrategy] = None
    # static chain geometry for the megakernel fusion pass — input dims,
    # pads, act/pool; what select_fused_segments and conv_chain consume
    geom: Optional[dispatch.ChainGeom] = None


@dataclasses.dataclass(frozen=True)
class UpsampleStep:
    factor: int
    method: str                     # "bilinear" | "nearest"


@dataclasses.dataclass(frozen=True)
class FlattenStep:
    pass


@dataclasses.dataclass(frozen=True)
class DenseStep:
    name: str
    wa: WASpec
    act: str


PlanStep = CAStep | ConvStep | UpsampleStep | FlattenStep | DenseStep


@dataclasses.dataclass(eq=False)
class CompiledPlan:
    """Everything ``execute`` needs, resolved once from shapes.

    ``report`` is the architecture-level power/latency/FPS-per-W report for
    one ``frame_shape`` frame — identical to what the eager interpreter
    recomputed on every call. A plan is batch-agnostic: ``execute`` accepts
    any leading batch dimension (each shape jit-compiles once).

    Calibration caveat (inherited from the eager reference, preserved for
    bit-identity): the CRC requant scale is a per-*tensor* max, reduced over
    the batch axis too, so a frame's logits depend on the other frames in
    its batch — serving the same frame at batch 1 vs batch 8 can classify
    differently. Per-frame accuracy numbers should be measured at the batch
    size they will be served at (or batch 1 for the hardware's per-frame
    semantics).
    """

    layers: tuple
    frame_shape: Tuple[int, int, int]         # per-frame [H, W, C]
    scheme: WASpec | MixedPrecisionScheme
    steps: Tuple[PlanStep, ...]
    schedules: Tuple[ocore.OCSchedule, ...]
    layer_specs: Tuple[WASpec, ...]
    report: pmod.ModelReport
    out_features: int
    consts: Dict[str, object] = dataclasses.field(default_factory=dict)
    # fused megakernel segments (runs of conv steps executing as one
    # launch each, see kernels.dispatch.select_fused_segments); resolved
    # at compile time, applied by the executor when calibration allows
    # (per-frame, or per-tensor at batch 1)
    fused_segments: Tuple[dispatch.FusedSegmentSpec, ...] = ()
    _exec_fns: Dict[str, object] = dataclasses.field(default_factory=dict,
                                                     repr=False)
    # has the analysis verifier run over this plan? (verify="auto" runs it
    # on first compile; "on" also re-checks cache hits — see _compile_model)
    _verified: bool = dataclasses.field(default=False, repr=False)

    def executor(self, per_frame: bool = False, donate: bool = False,
                 mesh: Optional[jax.sharding.Mesh] = None):
        """The jitted (params, frames) -> logits function for this plan.

        Keyed by the active kernel backend AND the Pallas interpret flag:
        both are baked in at trace time, so switching either (set_backend /
        REPRO_KERNEL_BACKEND / REPRO_FORCE_INTERPRET) gets its own jitted
        executable instead of silently reusing the old trace.

        ``per_frame`` keys a third trace family: the per-frame-calibrated
        executor (CRC requant scales reduced per frame, not per tensor)
        that the serving micro-batcher runs — see ``_crc_requant_traced``.

        ``donate`` keys a fourth: the frames argument's device buffer is
        donated to the computation, so XLA may reuse it instead of holding
        input and output live together — the serving device pool's
        host-memory pass. Only safe when the caller owns the frames array
        and never touches it again (a device-bound ``Executable`` stages
        its own input buffers, so it qualifies; the general ``run`` path
        must not, since callers may reuse what they passed).

        ``mesh`` keys a fifth: the batch axis split over the mesh's first
        axis, the steps run per shard under ``shard_map`` (Pallas kernels
        cannot be partitioned by the compiler; each shard runs them on its
        own frames) with the per-tensor calibration max reduced across
        shards.
        """
        key = (dispatch.get_backend(), dispatch.default_interpret(), per_frame,
               donate, mesh)
        fn = self._exec_fns.get(key)
        if fn is None:
            axis = mesh.axis_names[0] if mesh is not None else None

            def run(params, frames, consts):
                # runs once per trace: each is a compile the runtime pays
                obs.counter("plan.executor.traces").inc()
                return _execute_steps(
                    self.steps, params, frames, consts, per_frame=per_frame,
                    segments=self.fused_segments, axis_name=axis)

            if mesh is not None:
                P = jax.sharding.PartitionSpec
                run = jax.shard_map(run, mesh=mesh,
                                    in_specs=(P(), P(axis), P()),
                                    out_specs=P(axis), check_vma=False)
            fn = jax.jit(run, donate_argnums=(1,) if donate else ())
            self._exec_fns[key] = fn
        return fn


# ---------------------------------------------------------------------------
# Compile pass
# ---------------------------------------------------------------------------

_PLAN_CACHE: Dict[tuple, CompiledPlan] = {}
_CACHE_STATS = {"hits": 0, "misses": 0}


def plan_cache_stats() -> Dict[str, int]:
    return dict(_CACHE_STATS)


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    _CACHE_STATS["hits"] = 0
    _CACHE_STATS["misses"] = 0


def _compile_model(layers: Sequence, input_shape: Tuple[int, ...],
                   scheme: WASpec | MixedPrecisionScheme,
                   oc: ocore.OCConfig = ocore.DEFAULT_OC,
                   circuit: pmod.CircuitConstants = pmod.DEFAULT_CIRCUIT,
                   profile: pmod.AcceleratorProfile = pmod.LIGHTATOR_PROFILE,
                   weight_sram_kb: float = 512.0,
                   act_sram_kb: float = 256.0,
                   fc_batch: int = 1,
                   conv_strategy: Optional[str] = None,
                   conv_vmem_budget: Optional[int] = None,
                   fuse: Optional[str] = None,
                   verify: Optional[str] = None) -> CompiledPlan:
    """Resolve specs, shapes, OC schedules and the power report — once.

    ``input_shape`` is the frame shape, batched [B, H, W, C] or per-frame
    [H, W, C]. The schedule / report describe one frame and the plan is
    batch-agnostic (the device processes a frame per pass; the batch
    dimension only feeds the jitted execute pass), so plans are cached on
    the per-frame dims: streaming a ragged final batch or sweeping batch
    sizes reuses the same ``CompiledPlan`` object — and its jitted
    executors — without re-scheduling.

    ``fc_batch`` schedules FC layers at the served batch size: one weight
    mapping round streams ``fc_batch`` input vectors before remapping, so
    the DAC-settle remap cycles amortize across the batch. The report stays
    *per-frame* (FC cycles and remap cycles are divided back by
    ``fc_batch``); only the amortized terms change — per-cycle power
    breakdowns are scale-invariant in the batch. The default (1) is the
    seed's per-frame semantics, bit-identical to ``run_eager`` reports.

    ``conv_strategy`` / ``conv_vmem_budget`` pin the conv execution
    strategy explicitly (what ``repro.Options`` passes down); ``None``
    defers to the ``REPRO_CONV_STRATEGY`` / ``REPRO_CONV_VMEM_BUDGET`` env
    defaults. The cache key holds the *resolved* values, so an explicit
    option equal to the ambient env default hits the same cached plan.

    ``fuse`` pins the megakernel chain-fusion mode ("auto" | "on" | "off",
    what ``Options(fuse=...)`` passes down); ``None`` derives it from the
    resolved conv strategy mode (``dispatch.conv_fuse_mode``: forced
    resident/strip disable fusion, ``fused`` forces it on).

    ``verify`` pins the plan-verifier mode ("auto" | "on" | "off", what
    ``Options(verify=...)`` passes down; ``None`` defers to
    ``REPRO_VERIFY``, default "auto"). "auto" runs ``repro.analysis.
    verify_plan`` on every cache-miss compile and raises
    :class:`~repro.analysis.PlanVerificationError` at error severity
    (the plan is NOT cached — a later verify="off" compile starts
    clean); "on" additionally re-checks cache hits, so a plan first
    compiled under "off" still gets proved before use; "off" skips.
    Warning/error findings land in ``report.verification``. Like
    ``trace``, the mode stays OUT of the cache key: verification never
    changes what gets compiled, so verified and unverified callers
    share the same cached plan.
    """
    from repro.core.accelerator import (CASpec, ConvSpec, DenseSpec,
                                        FlattenSpec, UpsampleSpec)
    if fc_batch < 1:
        raise ValueError(f"fc_batch must be >= 1, got {fc_batch}")
    layers = tuple(layers)
    frame_shape = tuple(int(d) for d in input_shape[-3:])
    if len(frame_shape) != 3:
        raise ValueError(f"input_shape {input_shape} must be [B,H,W,C] or "
                         f"[H,W,C]")
    conv_mode = (conv_strategy if conv_strategy is not None
                 else dispatch.conv_strategy_mode())
    conv_budget = (conv_vmem_budget if conv_vmem_budget is not None
                   else dispatch.conv_vmem_budget())
    fuse_mode = fuse if fuse is not None else dispatch.conv_fuse_mode(conv_mode)
    from repro.analysis import verifier as _verifier
    verify_mode = verify if verify is not None else _verifier.verify_mode()
    if verify_mode not in _verifier.VERIFY_MODES:
        raise ValueError(f"unknown verify mode {verify_mode!r}; expected "
                         f"one of {_verifier.VERIFY_MODES}")
    key = (layers, frame_shape, scheme, oc, circuit, profile,
           weight_sram_kb, act_sram_kb, fc_batch,
           (conv_mode, conv_budget, fuse_mode))
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        _CACHE_STATS["hits"] += 1
        obs.counter("plan.cache.hit").inc()
        if obs.enabled():
            obs.event("plan.cache.hit",
                      attrs={"frame_shape": list(frame_shape),
                             "layers": len(layers)})
        if verify_mode == "on":
            # a hit may predate verification (first compiled under "off")
            _verify_plan(cached, conv_budget)
        return cached
    _CACHE_STATS["misses"] += 1
    obs.counter("plan.cache.miss").inc()
    with obs.span("plan.compile",
                  attrs={"frame_shape": list(frame_shape),
                         "layers": len(layers), "fc_batch": fc_batch,
                         "conv_strategy": conv_mode, "fuse": fuse_mode}):
        plan = _compile_model_uncached(
            layers, frame_shape, scheme, oc, circuit, profile,
            weight_sram_kb, act_sram_kb, fc_batch, conv_mode, conv_budget,
            fuse_mode)
    if verify_mode != "off":
        # verify BEFORE caching: a plan that fails at error severity is
        # never published, so a later verify="off" compile starts clean
        _verify_plan(plan, conv_budget)
    _PLAN_CACHE[key] = plan
    return plan


def _verify_plan(plan: CompiledPlan, budget: int) -> None:
    """Run the analysis verifier over ``plan`` once (idempotent).

    Warning/error findings are stored in ``plan.report.verification``
    (info-level headroom facts stay out — see ModelReport); error
    severity raises :class:`repro.analysis.PlanVerificationError`. A plan
    already verified re-raises from its stored findings instead of
    re-walking.
    """
    from repro import analysis
    if plan._verified:
        stored = plan.report.verification
        if any(d["severity"] == "error" for d in stored):
            raise analysis.PlanVerificationError(
                [analysis.Diagnostic(**d) for d in stored])
        return
    with obs.span("plan.verify", attrs={"layers": len(plan.layers)}):
        # info (the headroom report) never reaches ModelReport, so the
        # compile path skips constructing it (scripts/verify_plan.py asks
        # for it explicitly)
        diags = analysis.verify_plan(plan, budget=budget,
                                     include_info=False)
    plan.report.verification = [d.asdict() for d in diags
                                if d.severity != "info"]
    plan._verified = True
    obs.counter("plan.verify.run").inc()
    if analysis.errors(diags):
        obs.counter("plan.verify.error").inc()
        raise analysis.PlanVerificationError(diags)


def _compile_model_uncached(layers, frame_shape, scheme, oc, circuit,
                            profile, weight_sram_kb, act_sram_kb, fc_batch,
                            conv_mode, conv_budget,
                            fuse_mode) -> CompiledPlan:
    """The cache-miss body of :func:`_compile_model` (span-wrapped)."""
    from repro.core.accelerator import (CASpec, ConvSpec, DenseSpec,
                                        FlattenSpec, UpsampleSpec)
    compute_layers = [l for l in layers if isinstance(l, (ConvSpec, DenseSpec))]
    specs = resolve_layer_specs(len(compute_layers), scheme)
    spec_iter = iter(specs)

    steps: List[PlanStep] = []
    schedules: List[ocore.OCSchedule] = []
    spec_list: List[WASpec] = []

    h, w, c = frame_shape
    out_features = 0
    for layer in layers:
        if isinstance(layer, CASpec):
            if h % layer.pool or w % layer.pool:
                raise ValueError(
                    f"CA pool={layer.pool} does not divide frame "
                    f"{h}x{w}")
            h, w = h // layer.pool, w // layer.pool
            # fused RGB->gray collapses channels; per-channel pooling keeps c
            rgb = layer.rgb_to_gray if layer.rgb_to_gray is not None else (c == 3)
            c_out = 1 if (rgb or c == 1) else c
            schedules.append(ocore.schedule_ca(
                "CA", h, w, layer.pool, channels=frame_shape[-1], oc=oc))
            spec_list.append(WASpec(4, 4))
            steps.append(CAStep(layer.pool, rgb))
            c = c_out
        elif isinstance(layer, ConvSpec):
            wa = next(spec_iter)
            if layer.depthwise and layer.c_out != layer.c_in:
                raise ValueError(
                    f"{layer.name}: depthwise conv needs c_out == c_in "
                    f"(got {layer.c_in} -> {layer.c_out})")
            pads = jax.lax.padtype_to_pads(
                (h, w), (layer.kernel, layer.kernel),
                (layer.stride, layer.stride), layer.padding)
            pads = tuple((int(lo), int(hi)) for lo, hi in pads)
            h_out = conv_out_hw(h, layer.kernel, layer.stride, layer.padding)
            w_out = conv_out_hw(w, layer.kernel, layer.stride, layer.padding)
            # resident vs strip-mined, from the conv's own (pre-pool) output
            # dims — part of the plan AND the power report (serving surfaces)
            strat = dispatch.select_conv_strategy(
                h_out, w_out, layer.c_in, layer.c_out, layer.kernel,
                layer.stride, groups=layer.c_in if layer.depthwise else 1,
                mode=conv_mode, budget=conv_budget)
            geom = dispatch.ChainGeom(
                layer.name, h, w, layer.c_in, layer.c_out, layer.kernel,
                layer.stride, pads,
                groups=layer.c_in if layer.depthwise else 1,
                act=layer.act, pool=layer.pool)
            h, w, c = h_out, w_out, layer.c_out
            if layer.pool is not None:
                kind, size = layer.pool
                if h % size or w % size:
                    raise ValueError(
                        f"{layer.name}: {kind}-pool size={size} does not "
                        f"divide its {h}x{w} conv output (the eager path "
                        f"fails the same way, at reshape time)")
                h, w = h // size, w // size
                if kind == "avg":
                    # avg pooling runs on CA banks with pre-set weights —
                    # scheduled before the conv, as the eager interpreter did
                    schedules.append(ocore.schedule_ca(
                        f"{layer.name}.pool", h, w, size, channels=1, oc=oc))
                    spec_list.append(WASpec(4, 4))
            # NB: the eager interpreter scheduled the conv with its
            # *post-pool* output dims (it read y.shape after pooling);
            # reproduced here so reports stay bit-identical.
            # Depthwise: each output channel sees 1 input channel (k*k taps
            # per stride, c_out independent kernels).
            sched_c_in = 1 if layer.depthwise else layer.c_in
            schedules.append(ocore.schedule_conv(
                layer.name, h, w, sched_c_in, layer.c_out, layer.kernel,
                oc=oc))
            spec_list.append(wa)
            steps.append(ConvStep(layer.name, wa, layer.kernel, layer.stride,
                                  layer.act, layer.pool, pads,
                                  groups=layer.c_in if layer.depthwise else 1,
                                  strategy=strat, geom=geom))
        elif isinstance(layer, UpsampleSpec):
            if layer.method not in ("bilinear", "nearest"):
                raise ValueError(f"unknown upsample method {layer.method!r}")
            h, w = h * layer.factor, w * layer.factor
            # preset interpolation banks: weighted sums of <= 4 neighbours,
            # scheduled like the CA (no DACs, no remap rounds). Windows =
            # output pixels x channels (each channel interpolates
            # independently); name indexed so stacked upsamples stay distinct.
            taps = 2 if layer.method == "bilinear" else 1
            schedules.append(ocore.schedule_ca(
                f"upsample.{len(steps)}", h, w * c, taps, channels=1, oc=oc))
            spec_list.append(WASpec(4, 4))
            steps.append(UpsampleStep(layer.factor, layer.method))
        elif isinstance(layer, FlattenSpec):
            h, w, c = 1, 1, h * w * c
            steps.append(FlattenStep())
        elif isinstance(layer, DenseSpec):
            wa = next(spec_iter)
            schedules.append(ocore.schedule_fc(
                layer.name, layer.fan_in, layer.fan_out, batch=fc_batch,
                oc=oc))
            spec_list.append(wa)
            steps.append(DenseStep(layer.name, wa, layer.act))
            c = layer.fan_out
            out_features = layer.fan_out
        else:
            raise TypeError(f"unknown layer IR {layer!r}")

    power = pmod.PowerModel(oc, circuit, profile, weight_sram_kb, act_sram_kb)
    lps = []
    for s, sp in zip(schedules, spec_list):
        lp = power.layer_power(pmod.LayerSchedule(s, sp))
        if fc_batch > 1 and s.kind == "fc":
            # back to per-frame terms: one mapping round streamed fc_batch
            # input vectors, so the streaming cycles divide exactly and the
            # remap (DAC settle) cycles amortize. Per-cycle power rates are
            # batch-invariant, so the breakdown is untouched.
            lp.cycles = -(-lp.cycles // fc_batch)
            lp.remap_cycles = -(-lp.remap_cycles // fc_batch)
        lps.append(lp)
    report = power.finalize_report(lps, schedules, scheme)
    report.conv_strategy = {
        s.name: dataclasses.asdict(s.strategy) for s in steps
        if isinstance(s, ConvStep)}
    fused_segments = dispatch.select_fused_segments(
        [s.geom if isinstance(s, ConvStep) else None for s in steps],
        mode=fuse_mode, budget=conv_budget)
    report.fused_segments = [dataclasses.asdict(f) for f in fused_segments]

    # quantization divisors, fed to the executor as traced scalars (see the
    # bit-identity note at the top of this module)
    consts = {
        "a_qmax": np.float32((1 << ACT_BITS) - 1),
        "w_qmax": {s.name: np.float32(s.wa.w_qmax) for s in steps
                   if isinstance(s, (ConvStep, DenseStep))},
    }

    return CompiledPlan(layers, frame_shape, scheme, tuple(steps),
                        tuple(schedules), tuple(spec_list), report,
                        out_features or c, consts,
                        fused_segments=fused_segments)


# ---------------------------------------------------------------------------
# Execute pass (pure, jitted once per plan)
# ---------------------------------------------------------------------------

def step_name(step: PlanStep) -> str:
    """A plan step's stable name: the layer's own (``conv1_1``, ``fc8``)
    or its kind (``ca``, ``upsample``, ``flatten``). The executor runs
    each step under ``jax.named_scope`` of this name, so the compiled
    program's ``op_name`` metadata and a device trace name the step."""
    name = getattr(step, "name", None)
    if name is not None:
        return name
    return {CAStep: "ca", UpsampleStep: "upsample",
            FlattenStep: "flatten"}.get(type(step), type(step).__name__)


def segment_name(seg: dispatch.FusedSegmentSpec) -> str:
    """A fused segment's name: its steps' names joined by ``+``."""
    return "+".join(seg.names)


def _execute_steps(steps: Tuple[PlanStep, ...], params: Dict[str, Dict],
                   frames: jnp.ndarray, consts: Dict[str, object],
                   per_frame: bool = False,
                   segments: Tuple[dispatch.FusedSegmentSpec, ...] = (),
                   axis_name: Optional[str] = None) -> jnp.ndarray:
    """The device forward, batch-first, kernels via ``kernels.dispatch``.

    Numerics contract: bit-identical to ``LightatorDevice.run_eager`` (on
    the pallas backend, for CA-bearing models, up to the ca_pool float
    summation-order ulp — see the module docstring). The MAC accumulates
    are exact integers (so conv/dense kernel routing cannot change them);
    every dequant/activation/requant expression keeps the eager path's
    association order, with traced divisors + ``_nofma`` guards
    neutralizing the jit-only rewrites (see module-top note).

    ``per_frame`` switches every CRC requant to per-frame calibration
    (scale shape [B, 1, ...] instead of a batch-shared scalar): each
    frame's result becomes a pure function of that frame alone — the
    invariant the serving micro-batcher's pad/coalesce soundness rests on.
    Everything between requants is already per-frame independent (the MAC
    accumulates are exact integers, the dequant/activation chain is
    elementwise), so a frame served at any batch position is bit-identical
    to the same frame run at batch 1.

    ``segments`` are the plan's fused megakernel runs: when a run's start
    index comes up, its conv steps execute as ONE launch via
    ``dispatch.conv_chain`` (tap-loop accumulate + full fused epilogue
    per stage), bit-identical to the step-by-step path. The inter-stage
    CRC scale is a whole-frame reduction, so fusion applies only when
    frames are calibration-independent — per-frame mode, or per-tensor at
    batch 1 (the batch is static under jit, so this is a trace-time
    fallback, not a runtime branch).

    ``axis_name``: the steps run on one shard of a batch split over that
    mesh axis (``CompiledPlan.executor(mesh=)``); per-tensor scales reduce
    across shards, and a shard's batch of 1 is not the whole batch, so it
    does not license per-tensor fusion.
    """
    from repro.core.accelerator import _activation
    from repro.core.compressive import window_pool

    a_qmax = consts["a_qmax"]
    def requant(v):
        return _crc_requant_traced(v, a_qmax, per_frame, axis_name)

    with jax.named_scope("input"):
        codes, act_scale = requant(frames)
    x = codes
    fuse_ok = per_frame or (frames.shape[0] == 1 and axis_name is None)
    if segments and not fuse_ok:
        # per-tensor calibration at batch > 1 couples frames through the
        # batch-wide CRC max: the fused segments cannot run, and this
        # whole trace falls back to the per-layer path (trace-time event —
        # the jitted executable re-runs it for free afterwards)
        obs.counter("dispatch.fused.fallback").inc(len(segments))
        if obs.enabled():
            obs.event("dispatch.fused.fallback",
                      attrs={"segments": len(segments),
                             "batch": int(frames.shape[0])})
    seg_at = {s.start: s for s in segments} if fuse_ok else {}
    # NB: the spans below run at jit-TRACE time (this function executes
    # once per (backend, shape, calibration) trace family) — they profile
    # trace priming, one of serving's cold-start costs, not steady-state
    # device time (that is serve.batch.* territory).
    i, n = 0, len(steps)
    while i < n:
        step = steps[i]
        seg = seg_at.get(i)
        if seg is not None:
            with jax.named_scope(segment_name(seg)), \
                    obs.span("plan.trace.fused_segment",
                             attrs={"names": list(seg.names)}):
                stages = []
                for s in steps[i:i + seg.length]:
                    p = params[s.name]
                    wq, ws = _quantize_weight_traced(
                        p["w"], s.wa, consts["w_qmax"][s.name])
                    stages.append((s.geom, wq, ws, p.get("b")))
                x, act_scale = dispatch.conv_chain(x, act_scale, stages,
                                                   a_qmax, per_frame)
            i += seg.length
            continue
        with jax.named_scope(step_name(step)):
            if isinstance(step, CAStep):
                intens = x * act_scale
                g = dispatch.ca_acquire(intens, step.pool, step.rgb_to_gray)
                if g.ndim == 3:
                    g = g[..., None]
                x, act_scale = requant(g)
            elif isinstance(step, ConvStep):
                p = params[step.name]
                wq, ws = _quantize_weight_traced(p["w"], step.wa,
                                                 consts["w_qmax"][step.name])
                acc = dispatch.conv_int(x, wq, step.stride, step.pads,
                                        groups=step.groups,
                                        strategy=step.strategy)
                out = acc * (act_scale * ws.reshape(1, 1, 1, -1))
                if p.get("b") is not None:
                    out = _nofma(out) + p["b"]
                y = _activation(out, step.act)
                if step.pool is not None:
                    y = window_pool(y, *step.pool)
                x, act_scale = requant(y)
            elif isinstance(step, UpsampleStep):
                from repro.core.compressive import upsample_reconstruct
                intens = x * act_scale
                up = upsample_reconstruct(intens, step.factor, step.method)
                x, act_scale = requant(up)
            elif isinstance(step, FlattenStep):
                intens = x * act_scale
                flat = intens.reshape(intens.shape[0], -1)
                x, act_scale = requant(flat)
            elif isinstance(step, DenseStep):
                p = params[step.name]
                wq, ws = _quantize_weight_traced(p["w"], step.wa,
                                                 consts["w_qmax"][step.name])
                acc = dispatch.matmul_int(x, wq)
                out = acc * (act_scale * ws.reshape(1, -1))
                if p.get("b") is not None:
                    out = _nofma(out) + p["b"]
                if step.act != "none":
                    y = _activation(out, step.act)
                    x, act_scale = requant(y)
                else:
                    x, act_scale = out, jnp.asarray(1.0)
            else:
                raise TypeError(f"unknown plan step {step!r}")
        i += 1
    # dequantize the final stage (act_scale is 1.0 after a no-act dense, a
    # scalar per-tensor scale, or a [B, 1, ...] per-frame scale — all
    # broadcast-exact, and the per-tensor multiply is the seed expression)
    with jax.named_scope("output"):
        return x * act_scale


def _execute(plan: CompiledPlan, params: Dict[str, Dict],
             frames: jnp.ndarray, per_frame: bool = False,
             donate: bool = False,
             mesh: Optional[jax.sharding.Mesh] = None,
             consts: Optional[Dict] = None) -> jnp.ndarray:
    """Run ``frames`` [B, H, W, C] through a compiled plan.

    Returns logits [B, n] for classifier plans, or an image [B, H', W', C']
    for plans whose last step is spatial (the ``repro.imaging`` pipelines) —
    the dequantized intensities of the final CRC stage.

    ``per_frame`` selects the per-frame-calibrated executor (the serving
    micro-batcher's batch-composition-independent semantics — see
    ``_crc_requant_traced``); the default is the seed's per-tensor
    calibration.

    ``consts`` are the quantization divisors to pass the executor: the
    plan's own numpy scalars by default, or the same values already placed
    on the device (an ``Executable`` places them once, so a call sends
    none).

    The underlying function is jitted once per plan; repeated calls with the
    same frame shape reuse the XLA executable (no re-tracing, no
    re-scheduling — the schedules live on the plan).
    """
    if frames.ndim == 3:                       # single frame [H, W, C]
        frames = frames[None]
    if frames.ndim != 4 or tuple(frames.shape[1:]) != plan.frame_shape:
        raise ValueError(f"frames {frames.shape} do not match plan frame "
                         f"shape {plan.frame_shape}; expected "
                         f"[B, {', '.join(map(str, plan.frame_shape))}]")
    if consts is None:
        consts = plan.consts
    return plan.executor(per_frame, donate, mesh)(params, frames, consts)


# ---------------------------------------------------------------------------
# Back-compat shims
#
# ``core.program`` (Program / Options / Executable) is the public front door;
# these keep the PR-1 function API working, bit-identical (they call the very
# same internals the new API calls), with a one-shot DeprecationWarning.
# ---------------------------------------------------------------------------

_DEPRECATION_WARNED: set = set()


def _warn_deprecated(old: str, replacement: str,
                     doc: str = "docs/api.md") -> None:
    """One-shot-per-process DeprecationWarning (the shared shim helper —
    ``launch.serve`` reuses it with its own ``doc``)."""
    if old in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(old)
    warnings.warn(f"{old} is deprecated; use {replacement} "
                  f"(see {doc})", DeprecationWarning, stacklevel=3)


def compile_model(layers: Sequence, input_shape: Tuple[int, ...],
                  scheme: WASpec | MixedPrecisionScheme,
                  oc: ocore.OCConfig = ocore.DEFAULT_OC,
                  circuit: pmod.CircuitConstants = pmod.DEFAULT_CIRCUIT,
                  profile: pmod.AcceleratorProfile = pmod.LIGHTATOR_PROFILE,
                  weight_sram_kb: float = 512.0,
                  act_sram_kb: float = 256.0,
                  fc_batch: int = 1) -> CompiledPlan:
    """Deprecated shim over the compile pass — use ``repro.Program``.

    ``Program(layers, params, input_hwc).compile(Options(scheme=...))``
    resolves the same cached plan; this wrapper keeps the full PR-1
    signature (positional calls included) for existing callers and is
    regression-tested bit-identical to the new path.
    """
    _warn_deprecated(
        "core.plan.compile_model",
        "repro.Program(...).compile(repro.Options(scheme=...))")
    return _compile_model(layers, input_shape, scheme, oc=oc,
                          circuit=circuit, profile=profile,
                          weight_sram_kb=weight_sram_kb,
                          act_sram_kb=act_sram_kb, fc_batch=fc_batch)


def execute(plan: CompiledPlan, params: Dict[str, Dict],
            frames: jnp.ndarray) -> jnp.ndarray:
    """Deprecated shim over the execute pass — use ``Executable.run``."""
    _warn_deprecated("core.plan.execute",
                     "repro.Program(...).compile(...).run(frames)")
    return _execute(plan, params, frames)
