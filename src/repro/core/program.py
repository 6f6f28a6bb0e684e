"""repro.core.program — the one front door for optical programs.

Lightator's pitch is one device serving *versatile* workloads: CNN inference
and fixed-function imaging compile onto the same optical-core runtime. This
module gives them one uniform invocation, replacing three uncoordinated
conventions (``plan.compile_model`` kwargs, bare ``(layers, params)``
tuples, ``PIPELINES[name].build``) and four scattered ``REPRO_*`` env reads:

    Program     a value object bundling (layer IR, params, input frame
                shape, name). Built from models (``models.vision.
                vision_program`` / ``Program.from_model``), from imaging
                pipelines (``imaging.PIPELINES[name].program(h, w, c)`` /
                ``Program.from_pipeline``), or directly from IR + params.
                ``Program.then`` composes two programs into ONE program —
                an imaging chain (denoise -> edge_detect) compiles as a
                single ``CompiledPlan``, one jit, one power report.

    Options     every knob that was a ``compile_model`` kwarg or a
                ``REPRO_*`` env var, as explicit dataclass fields with
                env-var defaults: scheme, OC/circuit/profile/SRAM config,
                ``fc_batch``, kernel backend, Pallas interpret flag, conv
                strategy + VMEM budget, and batch sharding over local
                devices.

    Executable  ``program.compile(options)``: the cached ``CompiledPlan``
                plus the resolved options. ``.run(frames)`` executes
                batch-first under the options' backend/interpret pin (and
                shards the batch axis over a device mesh when asked),
                ``.report`` / ``.plan`` expose the power report and plan.

Quick start::

    import repro

    prog = repro.Program.from_pipeline("edge_detect", 64, 64, 3)
    exe = prog.compile(repro.Options(scheme=W4A4, backend="reference"))
    edges = exe.run(frames)                 # [B, 64, 64, 1]
    print(exe.report.kfps_per_w)

The old entry points (``plan.compile_model``, ``plan.execute``,
``LightatorDevice.run``) survive as deprecated shims that call the same
internals — bit-identical, regression-tested in tests/test_program_api.py.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis import verifier as _verifier
from repro.core import optical_core as ocore
from repro.core import plan as plan_mod
from repro.core import power_model as pmod
from repro.core.quant import W4A4, MixedPrecisionScheme, WASpec
from repro.kernels import dispatch


# ---------------------------------------------------------------------------
# Options
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Options:
    """Everything that shapes how a :class:`Program` compiles and runs.

    One documented code path for what used to be ``compile_model`` kwargs
    plus four scattered env vars. Every ``None`` field defers to the same
    env-var/auto default the old path used, resolved at compile/run time —
    so ``Options()`` is exactly the ambient behaviour, and an explicit
    value equal to the ambient default hits the same cached plan:

    ==================  =========================  =======================
    field               env default when ``None``  meaning
    ==================  =========================  =======================
    ``backend``         ``REPRO_KERNEL_BACKEND``   ``pallas`` | ``reference``
                        (else pallas on TPU)       kernel dispatch target
    ``interpret``       ``REPRO_FORCE_INTERPRET``  Pallas interpret flag
                        (else off on TPU)
    ``conv_strategy``   ``REPRO_CONV_STRATEGY``    ``auto`` | ``resident``
                        (else ``auto``)            | ``strip`` | ``fused``
    ``conv_vmem_budget``  ``REPRO_CONV_VMEM_BUDGET``  heuristic budget, bytes
    ``fuse``            derived from the conv      megakernel chain fusion:
                        strategy mode              ``auto`` | ``on`` | ``off``
    ``trace``           ``REPRO_TRACE``            obs span/event emission:
                        (else ``auto``)            ``auto`` | ``on`` | ``off``
    ``verify``          ``REPRO_VERIFY``           plan verifier (repro.
                        (else ``auto``)            analysis): ``auto`` |
                                                   ``on`` | ``off``
    ==================  =========================  =======================

    ``fuse`` controls the megakernel pass (``dispatch.
    select_fused_segments``): runs of chainable convs execute as ONE kernel
    launch each, bit-identical to the unfused path. ``auto`` fuses runs of
    >= 2 stages under the channel cap + VMEM budget; ``on`` fuses every
    legal run (singletons included); ``off`` disables. ``None`` derives the
    mode from the conv strategy: ``fused`` -> on, forced ``resident``/
    ``strip`` -> off, ``auto`` -> auto.

    ``trace`` mirrors ``fuse``'s tri-state: ``auto`` emits spans/events
    only while an :func:`repro.obs.enable` collector is installed (the
    default — zero overhead otherwise), ``on`` forces emission (lazily
    installing a collector), ``off`` suppresses it even when a collector
    is live. The pin is per-thread for the duration of ``compile``/``run``
    (``obs.use_mode``) and deliberately stays OUT of the plan cache key:
    tracing never changes what gets compiled, so traced and untraced
    callers share the same cached plan.

    ``verify`` mirrors the same tri-state for the compile-time plan
    verifier (``repro.analysis.verify_plan``: the ``|acc| < 2^24``
    integer-exactness proof, shape legality, strip/fusion VMEM audit —
    docs/analysis.md). ``auto`` (the default) verifies on every
    cache-miss compile and raises
    :class:`repro.analysis.PlanVerificationError` at error severity;
    ``on`` additionally re-checks cache hits (a plan first compiled
    under "off" still gets proved before use); ``off`` skips. Findings
    at warning severity land in ``Executable.report.verification``
    without raising. Like ``trace``, the mode stays OUT of the plan
    cache key — verification never changes what gets compiled.

    ``shard_batch`` shards ``Executable.run``'s batch axis over the local
    devices (or an explicit ``mesh``) via ``NamedSharding`` — a graceful
    no-op on a single device or when the batch does not divide the device
    count. Sharding never changes the numerics: the only cross-example
    reduction in the execute pass is the CRC calibration ``max``, which is
    order-independent.
    """

    scheme: WASpec | MixedPrecisionScheme = W4A4
    oc: ocore.OCConfig = ocore.DEFAULT_OC
    circuit: pmod.CircuitConstants = pmod.DEFAULT_CIRCUIT
    profile: pmod.AcceleratorProfile = pmod.LIGHTATOR_PROFILE
    weight_sram_kb: float = 512.0
    act_sram_kb: float = 256.0
    fc_batch: int = 1
    backend: Optional[str] = None
    interpret: Optional[bool] = None
    conv_strategy: Optional[str] = None
    conv_vmem_budget: Optional[int] = None
    fuse: Optional[str] = None
    trace: Optional[str] = None
    verify: Optional[str] = None
    shard_batch: bool = False
    mesh: Optional[jax.sharding.Mesh] = None

    def __post_init__(self):
        if self.fc_batch < 1:
            raise ValueError(f"fc_batch must be >= 1, got {self.fc_batch}")
        if self.backend is not None and self.backend not in dispatch.BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; expected "
                             f"one of {dispatch.BACKENDS}")
        if (self.conv_strategy is not None
                and self.conv_strategy not in dispatch.CONV_STRATEGIES):
            raise ValueError(
                f"unknown conv strategy {self.conv_strategy!r}; expected "
                f"one of {dispatch.CONV_STRATEGIES}")
        if self.conv_vmem_budget is not None and self.conv_vmem_budget <= 0:
            raise ValueError(f"conv_vmem_budget must be > 0, got "
                             f"{self.conv_vmem_budget}")
        if self.fuse is not None and self.fuse not in dispatch.FUSE_MODES:
            raise ValueError(f"unknown fuse mode {self.fuse!r}; expected "
                             f"one of {dispatch.FUSE_MODES}")
        if self.trace is not None and self.trace not in obs.TRACE_MODES:
            raise ValueError(f"unknown trace mode {self.trace!r}; expected "
                             f"one of {obs.TRACE_MODES}")
        if (self.verify is not None
                and self.verify not in _verifier.VERIFY_MODES):
            raise ValueError(f"unknown verify mode {self.verify!r}; "
                             f"expected one of {_verifier.VERIFY_MODES}")

    def resolve(self) -> "Options":
        """Fill every ``None`` field from its env-var/auto default.

        What ``compile``/``run`` actually act on — and what the serving
        header prints, so the operator sees the effective configuration,
        not the unresolved ``None``s.
        """
        return dataclasses.replace(
            self,
            backend=(self.backend if self.backend is not None
                     else dispatch.get_backend()),
            interpret=(self.interpret if self.interpret is not None
                       else dispatch.default_interpret()),
            conv_strategy=(self.conv_strategy if self.conv_strategy is not None
                           else dispatch.conv_strategy_mode()),
            conv_vmem_budget=(self.conv_vmem_budget
                              if self.conv_vmem_budget is not None
                              else dispatch.conv_vmem_budget()),
            fuse=(self.fuse if self.fuse is not None
                  else dispatch.conv_fuse_mode(self.conv_strategy)),
            trace=(self.trace if self.trace is not None
                   else obs.trace_mode()),
            verify=(self.verify if self.verify is not None
                    else _verifier.verify_mode()),
        )

    def describe(self) -> str:
        """One-line summary of the *resolved* options (serving headers)."""
        r = self.resolve()
        shard = ""
        if r.shard_batch:
            n = (r.mesh.devices.size if r.mesh is not None
                 else len(jax.local_devices()))
            shard = f" shard_batch={n}dev"
        vmem = (f"{r.conv_vmem_budget >> 20}MB"
                if r.conv_vmem_budget >= (1 << 20)
                else f"{r.conv_vmem_budget >> 10}KB")
        trace = f" trace={r.trace}" if r.trace != "auto" else ""
        verify = f" verify={r.verify}" if r.verify != "auto" else ""
        return (f"scheme={r.scheme.name} backend={r.backend} "
                f"interpret={r.interpret} conv={r.conv_strategy}"
                f"(vmem={vmem}) fuse={r.fuse} "
                f"fc_batch={r.fc_batch}{trace}{verify}{shard}")


# ---------------------------------------------------------------------------
# Program
# ---------------------------------------------------------------------------

def infer_output_hwc(layers: Sequence,
                     input_hwc: Tuple[int, int, int]) -> Tuple[int, int, int]:
    """Shape-infer a layer-IR program: input [H, W, C] -> output [H', W', C'].

    The same per-layer arithmetic the compile pass runs (dense outputs come
    back as ``(1, 1, fan_out)``) without scheduling anything — what
    :meth:`Program.then` uses to check chain compatibility. Pool/CA
    divisibility violations are *not* raised here; they surface with the
    compile pass's own error at ``Program.compile``.

    NB: keep the per-layer cases in lockstep with ``plan._compile_model``'s
    shape walk — ``tests/test_program_api.py`` pins the two against each
    other on every vision model and several pipelines.
    """
    from repro.core.accelerator import (CASpec, ConvSpec, DenseSpec,
                                        FlattenSpec, UpsampleSpec)
    h, w, c = input_hwc
    for layer in layers:
        if isinstance(layer, CASpec):
            h, w = h // layer.pool, w // layer.pool
            rgb = (layer.rgb_to_gray if layer.rgb_to_gray is not None
                   else c == 3)
            c = 1 if (rgb or c == 1) else c
        elif isinstance(layer, ConvSpec):
            h = plan_mod.conv_out_hw(h, layer.kernel, layer.stride,
                                     layer.padding)
            w = plan_mod.conv_out_hw(w, layer.kernel, layer.stride,
                                     layer.padding)
            c = layer.c_out
            if layer.pool is not None:
                h, w = h // layer.pool[1], w // layer.pool[1]
        elif isinstance(layer, UpsampleSpec):
            h, w = h * layer.factor, w * layer.factor
        elif isinstance(layer, FlattenSpec):
            h, w, c = 1, 1, h * w * c
        elif isinstance(layer, DenseSpec):
            h, w, c = 1, 1, layer.fan_out
        else:
            raise TypeError(f"unknown layer IR {layer!r}")
    return h, w, c


@dataclasses.dataclass(frozen=True, eq=False)
class Program:
    """A compilable optical program: layer IR + params + input frame shape.

    The uniform currency of the API — CNNs (:func:`models.vision.
    vision_program`), imaging pipelines (``PIPELINES[name].program``) and
    hand-written IR all become ``Program``s, and every one compiles and
    runs the same way::

        exe = program.compile(Options(scheme=MX_43))
        out = exe.run(frames)
    """

    layers: Tuple
    params: Dict[str, Dict]
    input_hwc: Tuple[int, int, int]
    name: str = "program"

    def __post_init__(self):
        object.__setattr__(self, "layers", tuple(self.layers))
        hwc = tuple(int(d) for d in self.input_hwc)
        if len(hwc) != 3:
            raise ValueError(f"input_hwc {self.input_hwc!r} must be "
                             f"(H, W, C)")
        object.__setattr__(self, "input_hwc", hwc)

    # -- constructors -----------------------------------------------------

    @classmethod
    def from_model(cls, name: str, key=None, params: Optional[Dict] = None
                   ) -> "Program":
        """A paper CNN by name (``lenet`` / ``vgg9`` / ``vgg16``) — see
        :func:`repro.models.vision.vision_program`."""
        from repro.models.vision import vision_program
        return vision_program(name, key=key, params=params)

    @classmethod
    def from_pipeline(cls, name: str, h: int, w: int, c: int = 3
                      ) -> "Program":
        """An imaging pipeline by registry name, built for [h, w, c]."""
        from repro.imaging import PIPELINES
        if name not in PIPELINES:
            raise ValueError(f"unknown pipeline {name!r}; choose from "
                             f"{sorted(PIPELINES)}")
        return PIPELINES[name].program(h, w, c)

    # -- composition ------------------------------------------------------

    @property
    def output_hwc(self) -> Tuple[int, int, int]:
        """The program's output frame shape (dense outputs: (1,1,n))."""
        return infer_output_hwc(self.layers, self.input_hwc)

    def then(self, other: "Program", name: Optional[str] = None) -> "Program":
        """Compose: this program's output feeds ``other``'s input.

        Returns ONE program — the concatenated IR compiles as a single
        ``CompiledPlan`` (one jit, one power report), which is how imaging
        chains (denoise -> edge_detect, compress -> recon -> sharpen) fuse
        at the program level instead of round-tripping through host memory
        between stages. ``other`` must have been built for this program's
        output shape. Layer names colliding with ours are suffixed
        (``grad`` -> ``grad.2``) in both the IR and the params, so chaining
        two instances of the same pipeline works.
        """
        out_hwc = self.output_hwc
        if tuple(other.input_hwc) != out_hwc:
            raise ValueError(
                f"cannot chain {self.name!r} -> {other.name!r}: output "
                f"{out_hwc} does not match {other.name!r}'s input "
                f"{tuple(other.input_hwc)}; rebuild the second program "
                f"for the first one's output shape")
        taken = {l.name for l in self.layers if hasattr(l, "name")}
        layers = list(self.layers)
        params = dict(self.params)
        for layer in other.layers:
            if hasattr(layer, "name"):
                new = layer.name
                i = 2
                while new in taken:
                    new, i = f"{layer.name}.{i}", i + 1
                taken.add(new)
                if new != layer.name:
                    if layer.name in other.params:
                        params[new] = other.params[layer.name]
                    layer = dataclasses.replace(layer, name=new)
                elif layer.name in other.params:
                    params[new] = other.params[layer.name]
            layers.append(layer)
        return Program(tuple(layers), params, self.input_hwc,
                       name=name or f"{self.name}>{other.name}")

    # -- compile ----------------------------------------------------------

    def compile(self, options: Optional[Options] = None) -> "Executable":
        """Static pass: resolve the (cached) plan under ``options``."""
        options = options or Options()
        with contextlib.ExitStack() as stack:
            if options.trace is not None:
                stack.enter_context(obs.use_mode(options.trace))
            plan = plan_mod._compile_model(
                self.layers, self.input_hwc, options.scheme, oc=options.oc,
                circuit=options.circuit, profile=options.profile,
                weight_sram_kb=options.weight_sram_kb,
                act_sram_kb=options.act_sram_kb, fc_batch=options.fc_batch,
                conv_strategy=options.conv_strategy,
                conv_vmem_budget=options.conv_vmem_budget,
                fuse=options.fuse, verify=options.verify)
        return Executable(self, options, plan)


# ---------------------------------------------------------------------------
# Executable
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class Executable:
    """A compiled program: ``CompiledPlan`` + the options it runs under.

    ``run`` is batch-first and jit-cached per (backend, interpret, shape)
    on the shared plan — two Executables over the same plan with different
    backends each get their own trace (the ``executor()`` keying), and the
    plan itself is shared through the global plan cache.
    """

    program: Program
    options: Options
    _plan: plan_mod.CompiledPlan
    _sharded_params: Optional[Dict] = dataclasses.field(
        default=None, repr=False)
    # the plan's quantization divisors placed once (see _placed_consts):
    # on the bound device, or the default one, and replicated over the mesh
    _device_consts: Optional[Dict] = dataclasses.field(
        default=None, repr=False)
    _sharded_consts: Optional[Dict] = dataclasses.field(
        default=None, repr=False)
    _report_copy: Optional[pmod.ModelReport] = dataclasses.field(
        default=None, repr=False)
    _mesh: Optional[jax.sharding.Mesh] = dataclasses.field(
        default=None, repr=False)
    # device-bound view state (see bind()): the committed target device,
    # the params replicated onto it, whether input device buffers are
    # donated to the computation, and the reusable host staging buffers
    # run_padded pads into — a ring of `_staging_slots` buffers per
    # (bucket, frame shape) key, rotated per use so a buffer is never
    # mutated while an async-dispatched batch may still read it
    _device: Optional[jax.Device] = dataclasses.field(
        default=None, repr=False)
    _device_params: Optional[Dict] = dataclasses.field(
        default=None, repr=False)
    _donate: bool = dataclasses.field(default=False, repr=False)
    _staging: Dict = dataclasses.field(default_factory=dict, repr=False)
    _staging_slots: int = dataclasses.field(default=2, repr=False)

    @property
    def plan(self) -> plan_mod.CompiledPlan:
        return self._plan

    @property
    def report(self) -> pmod.ModelReport:
        """The architecture power/latency report (per frame).

        A private copy: the plan (and its report) is shared process-wide
        through the plan cache, so callers mutating what they got back must
        not corrupt other Executables or future cache hits (the same guard
        the ``LightatorDevice.run`` shim applies).
        """
        if self._report_copy is None:
            import copy
            self._report_copy = copy.deepcopy(self._plan.report)
        return self._report_copy

    def _pinned(self) -> contextlib.ExitStack:
        """Enter the options' backend/interpret/trace pins (per-thread)."""
        stack = contextlib.ExitStack()
        if self.options.backend is not None:
            stack.enter_context(dispatch.use_backend(self.options.backend))
        if self.options.interpret is not None:
            stack.enter_context(dispatch.use_interpret(self.options.interpret))
        if self.options.trace is not None:
            stack.enter_context(obs.use_mode(self.options.trace))
        return stack

    def run(self, frames) -> jnp.ndarray:
        """Execute ``frames`` [B, H, W, C] (or one [H, W, C] frame).

        Returns logits [B, n] for classifier programs or an image
        [B, H', W', C'] for spatial programs. An explicit
        ``options.backend`` / ``options.interpret`` is pinned for the
        duration of the call; ``None`` fields keep deferring to the
        ambient ``set_backend`` / env state, exactly like the old path.
        """
        frames = jnp.asarray(frames)
        with self._pinned():
            frames, params, consts, mesh = self._placed(frames)
            return plan_mod._execute(self._plan, params, frames,
                                     consts=consts, mesh=mesh)

    def __call__(self, frames) -> jnp.ndarray:
        return self.run(frames)

    # -- device binding (the serving pool's per-device executables) -------

    @property
    def device(self) -> Optional[jax.Device]:
        """The committed target device (None: follow ambient placement)."""
        return self._device

    def bind(self, device, donate: Optional[bool] = None,
             staging_slots: int = 2) -> "Executable":
        """A device-committed view of this Executable (``repro.serve`` pool).

        The returned Executable shares this one's compiled plan (and jit
        cache) but commits execution to ``device``: frames are
        ``device_put`` there, and the params and the plan's quantization
        divisors (``plan.consts``, still traced arguments of the jitted
        executor) are placed on it once, at the first run, and cached, so
        a launch sends no host scalar to the device. It also enables the
        host-side serving optimizations:

        * ``run_padded`` pads into a **ring of reusable host staging
          buffers** per (bucket, frame-shape) instead of allocating +
          zero-filling a fresh array per batch. ``staging_slots`` is the
          ring depth: it must be >= the number of batches the caller may
          have async-dispatched but not yet awaited, plus one being
          staged — ``jax.device_put`` of a numpy array is not guaranteed
          to copy synchronously (zero-copy aliasing on CPU, lazy H2D
          elsewhere), so a buffer must not be rewritten until the batch
          that staged into it has materialized. The pool passes its
          per-device pipeline depth (``ServeConfig.max_inflight``); the
          default of 2 covers the worker's dispatch-then-await-previous
          overlap;
        * with ``donate`` (default: on everywhere except the CPU backend,
          which cannot alias the buffers and would warn), the frames'
          device buffer is **donated** to the computation, so XLA can
          reuse it rather than holding input and output live together.

        Both make the bound view unsafe for *shared-input* callers: the
        staging buffer means concurrent ``run_padded`` calls on one bound
        Executable race, and donation consumes whatever device array the
        run was given. The pool gives each device worker its own bound
        view and stages every input itself, so it satisfies both
        contracts; treat ``bind`` as the pool's seam, not a general API.
        ``shard_batch`` is ignored on a bound view (the batch is already
        placed on exactly one device).
        """
        if donate is None:
            donate = jax.default_backend() != "cpu"
        if staging_slots < 1:
            raise ValueError(
                f"staging_slots must be >= 1, got {staging_slots}")
        exe = Executable(self.program, self.options, self._plan)
        exe._device = device
        exe._donate = bool(donate)
        exe._staging_slots = int(staging_slots)
        return exe

    def _placed(self, frames: jnp.ndarray):
        """-> (frames, params, consts, mesh) placed for this Executable:
        committed to the bound device, or batch-sharded (mesh not None),
        or as is."""
        if self._device is not None:
            if self._device_params is None:
                self._device_params = jax.device_put(self.program.params,
                                                     self._device)
            return (jax.device_put(frames, self._device),
                    self._device_params, self._placed_consts(), None)
        return self._shard(frames)

    def _placed_consts(self):
        """The plan's quantization divisors as device arrays, placed once:
        committed to the bound device, or uncommitted on the default
        device (jit moves them wherever the frames are). They stay traced
        float32 arguments (the bit-identity note in ``core.plan``); as
        numpy scalars they were sent to the device again on every call."""
        if self._device_consts is None:
            self._device_consts = jax.device_put(self._plan.consts,
                                                 self._device)
            obs.counter("executable.consts.placed").inc()
        return self._device_consts

    # -- serving: per-frame calibration + batch buckets -------------------

    def run_per_frame(self, frames) -> jnp.ndarray:
        """Execute with *per-frame* CRC calibration (serving semantics).

        The seed-faithful :meth:`run` reduces every CRC requant scale over
        the whole tensor, batch axis included, so a frame's output depends
        on its batch neighbours. This variant reduces each scale over the
        frame's own axes instead — the hardware's frame-per-pass
        calibration: every frame's result is a pure function of that frame,
        so batch composition (and zero-padding) can never perturb it, and
        each frame is bit-identical to the same frame at batch 1 under
        either method. This is the executor ``repro.serve``'s micro-batcher
        coalesces requests onto.
        """
        return self.launch(self.place(frames))

    def place(self, frames):
        """Stage a batch for :meth:`launch`: ``jnp.asarray`` and the
        placement's ``device_put`` -> (frames, params, consts, mesh)."""
        return self._placed(jnp.asarray(frames))

    def launch(self, placed) -> jnp.ndarray:
        """The per-frame-calibrated jitted call on a batch from
        :meth:`place`; returns the lazy device result."""
        frames, params, consts, mesh = placed
        with self._pinned():
            return plan_mod._execute(self._plan, params, frames,
                                     per_frame=True, donate=self._donate,
                                     mesh=mesh, consts=consts)

    def compiled_text(self, bucket: int) -> str:
        """The compiled HLO text of the per-frame executor at batch
        ``bucket``, as :meth:`launch` runs it (compiled again, from the
        compile cache where there is one). Each instruction's ``op_name``
        names the plan step it computes (``jax.named_scope`` in
        ``core.plan``), so a profile's op names can be read by step. It
        lowers with the divisors :meth:`launch` passes, placed."""
        params = (self._device_params if self._device_params is not None
                  else self.program.params)
        spec = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        frames = jax.ShapeDtypeStruct((bucket, *self.program.input_hwc),
                                      jnp.float32)
        with self._pinned():
            fn = self._plan.executor(True, self._donate, None)
            return fn.lower(spec, frames,
                            self._placed_consts()).compile().as_text()

    def pad_chunks(self, frames, bucket: int):
        """Yield ``(chunk, real)``: ``frames`` zero-padded into
        ``bucket``-sized chunks, each padded as it is asked for, ``real``
        its count of real frames. See :meth:`run_padded`."""
        if bucket < 1:
            raise ValueError(f"bucket must be >= 1, got {bucket}")
        frames = np.asarray(frames, np.float32)
        if frames.ndim == 3:
            frames = frames[None]
        n = frames.shape[0]
        for off in range(0, n, bucket):
            chunk = frames[off:off + bucket]
            real = chunk.shape[0]
            if real < bucket:
                if self._device is not None:
                    key = (bucket, chunk.shape[1:])
                    ring = self._staging.setdefault(key, [])
                    if len(ring) < self._staging_slots:
                        buf = np.zeros((bucket, *chunk.shape[1:]),
                                       np.float32)
                    else:
                        # oldest slot: the batch that staged into it was
                        # awaited >= slots-1 dispatches ago
                        buf = ring.pop(0)
                    ring.append(buf)
                    buf[:real] = chunk
                    buf[real:] = 0.0
                    chunk = buf
                else:
                    chunk = np.concatenate(
                        [chunk, np.zeros((bucket - real, *chunk.shape[1:]),
                                         np.float32)])
            yield chunk, real

    def run_padded(self, frames, bucket: int) -> jnp.ndarray:
        """Padded-run helper: execute ``frames`` at a fixed batch bucket.

        Zero-pads the batch up to ``bucket`` (batches beyond it run in
        ``bucket``-sized chunks), executes per-frame-calibrated, and slices
        the real results back out — so a server always hits one of a few
        pre-compiled batch shapes instead of jit-tracing every queue
        length. Per-frame calibration severs every cross-frame data path,
        so the padding frames provably cannot change the real frames'
        results (bit-identical to batch-1 :meth:`run` calls per frame;
        regression-tested in tests/test_serve.py).

        A device-bound view (:meth:`bind`) pads into a ring of reusable
        host staging buffers per (bucket, frame shape) instead of
        allocating a fresh padded array every batch. The ring exists
        because ``jax.device_put`` of a numpy array need not copy
        synchronously (zero-copy aliasing on CPU, lazy H2D elsewhere):
        a pipelining pool worker dispatches batch N+1 before awaiting
        batch N, so N's buffer may still back N's in-flight computation
        while N+1 stages. Rotating ``staging_slots`` (>= pipeline depth)
        buffers guarantees a slot only comes back around after the batch
        that staged into it was awaited — each pool worker owns its
        bound view exclusively, so no further synchronization is needed,
        and pad content is provably inert either way (it cannot reach
        the real frames' results). Only the final chunk of an oversized
        batch can be partial, so one call uses at most one slot.

        It is :meth:`pad_chunks`, then :meth:`place` and :meth:`launch` of
        each chunk: the serving pool runs those steps itself to time
        each one.
        """
        outs = [self.launch(self.place(chunk))[:real]
                for chunk, real in self.pad_chunks(frames, bucket)]
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)

    def warm(self, buckets: Sequence[int] = (1,)) -> "Executable":
        """Trace + compile the per-frame executor at each bucket size.

        Serving warm-up: the first request at a new batch shape otherwise
        pays the full jit trace. Runs a zero batch per bucket and blocks,
        so device caches are primed too. Returns ``self`` for chaining.
        """
        h, w, c = self.program.input_hwc
        for b in sorted({int(b) for b in buckets}):
            if b < 1:
                raise ValueError(f"bucket must be >= 1, got {b}")
            self.run_per_frame(
                jnp.zeros((b, h, w, c), jnp.float32)).block_until_ready()
        return self

    # -- batch sharding ---------------------------------------------------

    def _shard(self, frames: jnp.ndarray):
        """Shard the batch axis over local devices
        -> (frames, params, consts, mesh).

        No-op (mesh None) unless ``options.shard_batch``, there are >= 2
        devices, and the batch divides the device count — the
        single-device laptop path is byte-for-byte the unsharded one.
        Params and the plan's divisors are replicated once (they are
        small: filter taps / CNN weights, scalars), frames are split on
        axis 0, and the executor runs per shard under ``shard_map`` (see
        ``CompiledPlan.executor``).
        """
        params = self.program.params
        if not self.options.shard_batch or frames.ndim != 4:
            return frames, params, self._placed_consts(), None
        if self._mesh is None:
            mesh = self.options.mesh
            if mesh is None:
                if len(jax.local_devices()) <= 1:
                    return frames, params, self._placed_consts(), None
                mesh = jax.sharding.Mesh(
                    np.asarray(jax.local_devices()), ("batch",))
            self._mesh = mesh          # invariant for this Executable
        mesh = self._mesh
        # the batch axis rides the mesh's FIRST axis (whatever the caller
        # named it); divisibility is against that axis alone
        axis = mesh.axis_names[0]
        n = mesh.shape[axis]
        if n <= 1 or frames.shape[0] % n != 0:
            return frames, params, self._placed_consts(), None
        P = jax.sharding.PartitionSpec
        frames = jax.device_put(
            frames, jax.sharding.NamedSharding(mesh, P(axis)))
        if self._sharded_params is None:
            replicated = jax.sharding.NamedSharding(mesh, P())
            self._sharded_params = jax.device_put(params, replicated)
            self._sharded_consts = jax.device_put(self._plan.consts,
                                                  replicated)
            obs.counter("executable.consts.placed").inc()
        return frames, self._sharded_params, self._sharded_consts, mesh
