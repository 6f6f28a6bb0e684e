"""Backend dispatch for the Lightator compute kernels.

One place decides *how* the photonic integer math actually runs:

  pallas     — the Pallas TPU kernels (photonic_mvm / conv_bank / ca_pool).
               On a real TPU they compile to MXU code; elsewhere they run in
               interpret mode, which is a correctness tool, not a perf path.
  reference  — the pure-jnp oracles (ref.py modules / core.compressive).
               Bit-identical to the Pallas kernels for the integer MVM path
               (both accumulate exact integers), and fast under XLA on CPU.

Selection order:

  1. ``set_backend("pallas"|"reference"|None)`` — programmatic override
     (``repro.Options(backend=...)`` routes through here for the duration
     of an ``Executable.run``).
  2. ``REPRO_KERNEL_BACKEND`` env var.
  3. default: ``pallas`` on TPU, ``reference`` everywhere else.

``default_interpret()`` is the single source of truth for the Pallas
``interpret=`` flag (previously three duplicated ``_INTERPRET`` module
globals): interpret off on TPU, on elsewhere, overridable for debugging with
``REPRO_FORCE_INTERPRET=1|0``.

Conv strategy (pallas backend only — the reference backend is always
``lax.conv_general_dilated``):

  resident   — im2col into the photonic MVM kernel: the whole patch matrix
               is materialized (k*k x the input), right for the paper's
               <=32x32 evaluation frames where everything fits on-chip.
  strip      — the strip-mined conv_bank kernel: output rows are tiled into
               strips, each input strip + (k-1)-row halo is DMA'd into VMEM
               once and reused across output-channel blocks; no patch matrix
               ever exists. The large-frame path (VGG16/AlexNet layers,
               >=256x256 sensor frames) and the native depthwise path.
  auto       — per-conv VMEM-budget heuristic (``select_conv_strategy``):
               strip when the per-frame im2col patch matrix would blow the
               budget, and always for depthwise (the strip kernel replaces
               the grouped per-channel im2col loop outright).

``REPRO_CONV_STRATEGY=auto|resident|strip|fused`` forces the choice
globally; ``REPRO_CONV_VMEM_BUDGET`` (bytes) resizes the heuristic's budget.

Chain fusion (the megakernel path):

  fused      — runs of chainable convs execute as ONE kernel launch per
               segment: every intermediate stays in VMEM (pallas) or inside
               one fused XLA computation (reference), with the requant +
               activation epilogue fused after each stage instead of
               round-tripping through HBM between layers.
               ``select_fused_segments`` picks the runs; ``conv_chain``
               executes one. The inter-stage CRC requant scale is a
               *whole-frame* max, so a fused segment processes whole frames
               stage-by-stage inside the launch (a stage barrier, not a
               halo-grown strip pyramid) — which is also why the fused path
               only runs under per-frame calibration or batch 1: per-tensor
               calibration at batch > 1 couples frames through the
               batch-wide max, and the executor falls back to the unfused
               per-layer path (bit-identical by construction).
               ``REPRO_CONV_STRATEGY=fused`` (or ``Options(fuse="on")``)
               forces every legal run to fuse; ``auto`` fuses only runs
               whose stages are small enough that the tap-loop formulation
               also wins on the reference/CPU backend.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from typing import Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs

BACKENDS = ("pallas", "reference")
CONV_STRATEGIES = ("auto", "resident", "strip", "fused")
FUSE_MODES = ("auto", "on", "off")

# Heuristic budget: what we let one conv's working set claim of the ~16 MB
# VMEM. Half goes to the strip (input rows + halo), the rest covers the
# weight block, accumulator and pipelining headroom.
DEFAULT_CONV_VMEM_BUDGET = 4 << 20

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")

# Programmatic overrides are *thread-local*: an Executable pinning its
# backend/interpret for the duration of a run must not leak the pin into
# (or have it clobbered by) a concurrently-running Executable on another
# thread of a threaded server.
_overrides = threading.local()


def default_interpret() -> bool:
    """Pallas ``interpret=`` flag: False on real TPU, True elsewhere.

    Resolution order: ``set_interpret`` / ``use_interpret`` programmatic
    override (what ``repro.Options(interpret=...)`` maps to; per-thread),
    then the ``REPRO_FORCE_INTERPRET`` env var (``1`` forces interpret mode
    even on TPU for debugging, ``0`` forces compiled mode), then the
    platform.
    """
    override = getattr(_overrides, "interpret", None)
    if override is not None:
        return override
    env = os.environ.get("REPRO_FORCE_INTERPRET", "").strip().lower()
    if env in _TRUTHY:
        return True
    if env in _FALSY:
        return False
    return jax.default_backend() != "tpu"


def set_interpret(value: Optional[bool]) -> None:
    """Force the Pallas interpret flag; ``None`` restores auto-selection."""
    _overrides.interpret = value


@contextlib.contextmanager
def use_interpret(value: bool) -> Iterator[None]:
    """Context manager form of :func:`set_interpret` (per-thread)."""
    prev = getattr(_overrides, "interpret", None)
    set_interpret(value)
    try:
        yield
    finally:
        set_interpret(prev)


def get_backend() -> str:
    """Resolve the active kernel backend (see module docstring)."""
    override = getattr(_overrides, "backend", None)
    if override is not None:
        return override
    env = os.environ.get("REPRO_KERNEL_BACKEND", "").strip().lower()
    if env:
        if env not in BACKENDS:
            raise ValueError(
                f"REPRO_KERNEL_BACKEND={env!r}; expected one of {BACKENDS}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def set_backend(name: Optional[str]) -> None:
    """Force a backend programmatically; ``None`` restores auto-selection."""
    if name is not None and name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; expected {BACKENDS}")
    _overrides.backend = name


@contextlib.contextmanager
def use_backend(name: str) -> Iterator[None]:
    """Context manager form of :func:`set_backend` (per-thread)."""
    prev = getattr(_overrides, "backend", None)
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


# ---------------------------------------------------------------------------
# Conv strategy selection (resident vs strip-mined)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ConvStrategy:
    """A resolved conv execution strategy + its strip geometry.

    ``strip_rows`` is output rows per strip; ``n_strips`` tiles the output
    height (the last strip may be padding, sliced off after the kernel).
    Both are 0 for the resident strategy.
    """

    kind: str                     # "resident" | "strip"
    strip_rows: int = 0
    n_strips: int = 0


def conv_strategy_mode() -> str:
    """The forced/auto strategy mode: ``REPRO_CONV_STRATEGY`` or ``auto``."""
    env = os.environ.get("REPRO_CONV_STRATEGY", "").strip().lower()
    if not env:
        return "auto"
    if env not in CONV_STRATEGIES:
        raise ValueError(
            f"REPRO_CONV_STRATEGY={env!r}; expected one of {CONV_STRATEGIES}")
    return env


def conv_vmem_budget() -> int:
    """Heuristic VMEM budget in bytes (``REPRO_CONV_VMEM_BUDGET`` override)."""
    env = os.environ.get("REPRO_CONV_VMEM_BUDGET", "").strip()
    if env:
        try:
            budget = int(env)
        except ValueError:
            raise ValueError(
                f"REPRO_CONV_VMEM_BUDGET={env!r} is not an integer; "
                f"expected a byte count like 4194304") from None
        if budget <= 0:
            raise ValueError(f"REPRO_CONV_VMEM_BUDGET={env!r} must be > 0")
        return budget
    return DEFAULT_CONV_VMEM_BUDGET


def _strip_geometry(h_out: int, w_out: int, c_in: int, kernel: int,
                    stride: int, budget: int) -> ConvStrategy:
    """Largest strip (output rows) whose double-buffered input strip + halo
    scratch fits budget/2, priced as the TPU compiler lays VMEM out (lanes
    padded to 128, sublanes to 8 — ``strip_kernel.vmem_row_bytes``)."""
    from repro.kernels.conv_bank.strip_kernel import vmem_row_bytes
    wp = (w_out - 1) * stride + kernel        # padded input width
    row_bytes = 2 * vmem_row_bytes(wp, c_in)  # two scratch slots, f32 codes
    # input rows needed for r output rows: (r-1)*stride + kernel
    rows = (budget // 2 // row_bytes - kernel) // stride + 1
    rows = max(1, min(int(rows), h_out))
    if rows >= 8:
        rows -= rows % 8                      # f32 sublane-friendly strips
    n_strips = -(-h_out // rows)
    return ConvStrategy("strip", rows, n_strips)


def select_conv_strategy(h_out: int, w_out: int, c_in: int, c_out: int,
                         kernel: int, stride: int = 1, groups: int = 1,
                         mode: Optional[str] = None,
                         budget: Optional[int] = None) -> ConvStrategy:
    """Resolve the conv strategy for one layer's geometry.

    ``h_out``/``w_out`` are the conv's own output dims (pre-pooling);
    ``c_in`` counts *all* input channels (also for depthwise, where the
    whole channel stack rides in each strip). Resolution order: explicit
    ``mode`` arg > ``REPRO_CONV_STRATEGY`` > VMEM-budget heuristic. The
    heuristic sends a conv to the strip path when its per-frame im2col
    patch matrix (h_out*w_out*k*k*c_in f32) would not sit in the budget,
    and sends depthwise convs there unconditionally — the strip kernel's
    per-tap VPU accumulate replaces the per-channel im2col loop.
    """
    mode = mode if mode is not None else conv_strategy_mode()
    if mode not in CONV_STRATEGIES:
        raise ValueError(
            f"unknown conv strategy {mode!r}; expected {CONV_STRATEGIES}")
    budget = budget if budget is not None else conv_vmem_budget()
    if mode == "resident":
        return ConvStrategy("resident")
    if mode in ("auto", "fused"):
        # "fused" is a *chain* mode (select_fused_segments); the per-conv
        # fallback strategy — what runs when a conv is outside every fused
        # segment, or fusion is disabled at runtime — resolves as auto
        depthwise = groups > 1 and groups == c_in
        patch_bytes = h_out * w_out * kernel * kernel * c_in * 4
        if not depthwise and patch_bytes <= budget:
            return ConvStrategy("resident")
    return _strip_geometry(h_out, w_out, c_in, kernel, stride, budget)


# ---------------------------------------------------------------------------
# Chain fusion: segment selection (the megakernel path)
# ---------------------------------------------------------------------------

# Auto-fusion channel cap: the fused tap-loop formulation (k*k shifted
# slice-matmul accumulates) beats the per-layer conv for the small channel
# counts of imaging chains and early CNN layers on every backend, but loses
# to a tuned dense conv once both channel dims are large. Measured on CPU
# XLA, the crossover sits near c_in*c_out ~ 1-2K; past it, auto leaves the
# run unfused ("on" ignores the cap — the caller asked for one launch).
FUSED_AUTO_CHANNEL_CAP = 2048

# Activations the fused epilogue supports. tanh is excluded: the fused and
# unfused paths must stay bit-identical, and a transcendental evaluated
# inside a Pallas kernel is not guaranteed to match XLA's lowering bit for
# bit the way the piecewise relu/abs/sign are.
FUSABLE_ACTS = ("relu", "abs", "sign", "none")


@dataclasses.dataclass(frozen=True)
class ChainGeom:
    """One conv stage's static geometry, as seen by the fusion pass.

    ``h_in``/``w_in`` are the stage's *input* dims (pre-padding);
    ``pads`` is the resolved ((lo, hi), (lo, hi)) spatial padding; ``pool``
    is the post-activation pool spec (kind, size) or None — all exactly what
    the plan's ``ConvStep`` carries, so the compile pass and the eager
    interpreter resolve identical segments from identical walks.
    """

    name: str
    h_in: int
    w_in: int
    c_in: int
    c_out: int
    kernel: int
    stride: int
    pads: Tuple[Tuple[int, int], Tuple[int, int]]
    groups: int = 1
    act: str = "relu"
    pool: Optional[Tuple[str, int]] = None

    @property
    def depthwise(self) -> bool:
        return self.groups > 1 and self.groups == self.c_in \
            and self.c_out == self.groups

    def out_hw(self) -> Tuple[int, int]:
        (plo, phi), (qlo, qhi) = self.pads
        h = (self.h_in + plo + phi - self.kernel) // self.stride + 1
        w = (self.w_in + qlo + qhi - self.kernel) // self.stride + 1
        if self.pool is not None:
            h, w = h // self.pool[1], w // self.pool[1]
        return h, w

    def stage_bytes(self) -> int:
        """f32 working set of this stage inside the megakernel, as the TPU
        lays VMEM out (lanes padded to 128, sublanes to 8): zero-bordered
        input frame + output frame + weight block."""
        from repro.kernels.conv_bank.strip_kernel import vmem_row_bytes
        (plo, phi), (qlo, qhi) = self.pads
        hp, wp = self.h_in + plo + phi, self.w_in + qlo + qhi
        in_b = hp * vmem_row_bytes(wp, self.c_in)
        h_out = (hp - self.kernel) // self.stride + 1
        w_out = (wp - self.kernel) // self.stride + 1
        out_b = h_out * vmem_row_bytes(w_out, self.c_out)
        w_b = self.kernel * self.kernel * vmem_row_bytes(
            self.c_in // self.groups, self.c_out)
        return in_b + out_b + w_b


@dataclasses.dataclass(frozen=True)
class FusedSegmentSpec:
    """A resolved fused run: ``length`` consecutive conv steps starting at
    plan-step index ``start`` execute as one kernel launch.

    ``halo_rows`` is the chain's input-halo growth: the extra input rows one
    output row needs through every stage (the strip formulation's per-strip
    overfetch — sum of (k-1) per stride-1 stage, compounded by strides and
    pools). ``vmem_bytes`` is the peak per-stage f32 working set.
    """

    start: int
    names: Tuple[str, ...]
    halo_rows: int
    vmem_bytes: int

    @property
    def length(self) -> int:
        return len(self.names)


def conv_fuse_mode(strategy_mode: Optional[str] = None) -> str:
    """Resolve the chain-fusion mode from the conv strategy mode.

    ``fused`` forces fusion on ("on": every legal run, any length);
    ``resident``/``strip`` force it off (the caller pinned a per-conv
    execution plan — honoring it means no cross-conv fusion); ``auto``
    defers to the heuristic (runs of >= 2 cheap stages fuse).
    """
    mode = (strategy_mode if strategy_mode is not None
            else conv_strategy_mode())
    if mode == "fused":
        return "on"
    if mode in ("resident", "strip"):
        return "off"
    return "auto"


def _chain_halo_rows(geoms: Sequence[ChainGeom]) -> int:
    """Input rows one output row needs through the chain, minus one.

    Back-substitution of the per-stage row recurrence
    ``rows_in = (rows_out - 1) * stride + kernel`` (pool expands
    ``rows_out`` by its window first) from the last stage to the first.
    """
    rows = 1
    for g in reversed(tuple(geoms)):
        if g.pool is not None:
            rows *= g.pool[1]
        rows = (rows - 1) * g.stride + g.kernel
    return rows - 1


def _fusable(g: ChainGeom, budget: int, auto: bool) -> bool:
    if g.groups != 1 and not g.depthwise:
        return False                       # general grouped convs: unfused
    if g.act not in FUSABLE_ACTS:
        return False
    if g.pool is not None and g.pool[0] not in ("max", "avg"):
        return False
    if auto:
        if not g.depthwise and g.c_in * g.c_out > FUSED_AUTO_CHANNEL_CAP:
            return False
        if g.stage_bytes() > budget:
            return False
    return True


def select_fused_segments(geoms: Sequence[Optional[ChainGeom]],
                          mode: Optional[str] = None,
                          budget: Optional[int] = None
                          ) -> Tuple[FusedSegmentSpec, ...]:
    """Segment a plan's step list into fusable conv runs.

    ``geoms`` is aligned with the plan's steps: a :class:`ChainGeom` for
    every conv step, ``None`` for everything else (CA, upsample, flatten,
    dense — all of which break a run). ``mode`` is a fuse mode ("auto" |
    "on" | "off"; default :func:`conv_fuse_mode` from the environment):
    auto fuses maximal runs of >= 2 stages that pass the channel cap and
    VMEM budget; "on" fuses every legal run including singletons (the
    epilogue still fuses into the single launch); "off" returns no
    segments.
    """
    mode = mode if mode is not None else conv_fuse_mode()
    if mode not in FUSE_MODES:
        raise ValueError(f"unknown fuse mode {mode!r}; expected {FUSE_MODES}")
    if mode == "off":
        return ()
    budget = budget if budget is not None else conv_vmem_budget()
    auto = mode == "auto"
    min_len = 2 if auto else 1
    segments, run_start, run = [], 0, []
    def _flush():
        if len(run) >= min_len:
            segments.append(FusedSegmentSpec(
                run_start, tuple(g.name for g in run),
                _chain_halo_rows(run),
                max(g.stage_bytes() for g in run)))
        run.clear()
    for i, g in enumerate(geoms):
        if g is not None and _fusable(g, budget, auto):
            if not run:
                run_start = i
            run.append(g)
        else:
            _flush()
    _flush()
    return tuple(segments)


# ---------------------------------------------------------------------------
# Dispatch entry points
# ---------------------------------------------------------------------------

def matmul_int(a_codes: jnp.ndarray, wq: jnp.ndarray) -> jnp.ndarray:
    """Integer-exact MAC: [M, K] codes x [K, N] weight levels -> f32 [M, N].

    This is the raw OC accumulate (arm dots + BPD + summation tree) with NO
    dequant — both backends return the exact integer sum carried in f32, so
    callers can apply scale factors in whatever association order their
    reference semantics demand. Exactness envelope: with the device's CRC
    codes (<= 15) and MR levels (|wq| <= 7, i.e. w_bits <= 4) every partial
    sum stays below 105 * K; for K up to ~160K that is under 2^24, exact in
    f32 and int32 alike. Callers pushing w_bits to 8 (|wq| <= 127, bound
    15 * 127 * K) must keep K below ~8.8K themselves.
    """
    if get_backend() == "pallas":
        from repro.kernels.photonic_mvm.ops import photonic_mvm_prequant
        ones = jnp.ones((wq.shape[-1],), jnp.float32)
        return photonic_mvm_prequant(a_codes.astype(jnp.int8),
                                     wq.astype(jnp.int8), ones, act_scale=1.0)
    from repro.kernels.photonic_mvm.ref import mvm_int_ref
    ones = jnp.ones((wq.shape[-1],), jnp.float32)
    return mvm_int_ref(a_codes.astype(jnp.int32), wq.astype(jnp.int32), ones)


def conv_int(codes: jnp.ndarray, wq: jnp.ndarray, stride: int,
             pads, groups: int = 1,
             strategy: Optional[ConvStrategy] = None) -> jnp.ndarray:
    """Integer-exact conv accumulate: [B,H,W,Cin] codes x [k,k,Cin/g,Cout]
    weight levels -> f32 [B,H',W',Cout], NO dequant (see matmul_int).

    ``groups`` is the feature-group count (1 = dense conv; groups == Cin with
    [k,k,1,Cin] weights = depthwise — the imaging pipelines' per-channel
    fixed-function filters: each channel is an independent single-channel
    kernel on the OC banks).

    ``strategy`` picks the pallas execution plan (see module docstring):
    ``None`` resolves per call via :func:`select_conv_strategy` (env /
    VMEM-budget heuristic); ``core.plan`` passes the strategy it resolved
    and recorded at compile time. The reference backend ignores it —
    ``lax.conv_general_dilated`` on the float-carried codes is the exact op
    the eager interpreter runs; a depthwise conv runs as the tap loop of
    ``conv_bank.ref.conv_taps_int`` instead, because XLA:TPU's grouped
    convolution does not return the exact integer accumulate (on a v5e,
    nearly every output of a 5x5 depthwise conv over 256² RGB codes was
    off, by up to 1.4e3). Both pallas strategies accumulate the same
    exact integers, so strategy choice can never change the results.
    """
    k, _, cg, c_out = wq.shape
    if c_out % groups or codes.shape[-1] != cg * groups:
        raise ValueError(
            f"conv_int: groups={groups} must divide c_out={c_out} and "
            f"match c_in={codes.shape[-1]} against weight slice {cg}")
    if get_backend() == "pallas":
        (plo, phi), (qlo, qhi) = pads
        h_out = (codes.shape[1] + plo + phi - k) // stride + 1
        w_out = (codes.shape[2] + qlo + qhi - k) // stride + 1
        if strategy is None:
            strategy = select_conv_strategy(h_out, w_out, codes.shape[-1],
                                            c_out, k, stride, groups)
        # dispatch.conv.* counters tick at jit-TRACE time: they count how
        # many conv layers each strategy was chosen for (per compiled
        # trace), not per-batch executions — see docs/observability.md
        obs.counter(f"dispatch.conv.{strategy.kind}").inc()
        if strategy.kind == "strip":
            return _conv_int_strip(codes, wq, stride, pads, groups, strategy,
                                   h_out)
        b = codes.shape[0]
        if groups == 1:
            patches, h_out, w_out = _im2col(codes, k, stride, pads)
            acc = matmul_int(patches, wq.reshape(k * k * cg, c_out))
            return acc.reshape(b, h_out, w_out, c_out)
        og = c_out // groups
        outs = []
        for g in range(groups):
            patches, h_out, w_out = _im2col(
                codes[..., g * cg:(g + 1) * cg], k, stride, pads)
            acc = matmul_int(patches,
                             wq[..., g * og:(g + 1) * og].reshape(
                                 k * k * cg, og))
            outs.append(acc.reshape(b, h_out, w_out, og))
        return jnp.concatenate(outs, axis=-1)
    obs.counter("dispatch.conv.reference").inc()
    if groups > 1 and cg == 1 and groups == c_out:
        from repro.kernels.conv_bank.ref import conv_taps_int
        return conv_taps_int(codes, wq, k, stride, pads, depthwise=True)
    return jax.lax.conv_general_dilated(
        codes.astype(jnp.float32), wq.astype(jnp.float32),
        window_strides=(stride, stride), padding=tuple(pads),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def _conv_int_strip(codes: jnp.ndarray, wq: jnp.ndarray, stride: int, pads,
                    groups: int, strat: ConvStrategy,
                    h_out: int) -> jnp.ndarray:
    """Raw integer accumulate through the strip-mined conv_bank kernels.

    Pads the rows so ``n_strips`` strips tile exactly (zero rows contribute
    zero partials; the surplus output rows are sliced off), then routes:
    dense -> MXU strip kernel; depthwise -> VPU strip kernel; general
    grouped -> one dense strip call per group slice.
    """
    from repro.kernels.conv_bank import strip_kernel as SK
    k, _, cg, c_out = wq.shape
    (plo, phi), (qlo, qhi) = pads
    xp = SK.pad_rows_for_strips(
        jnp.pad(codes, ((0, 0), (plo, phi), (qlo, qhi), (0, 0))),
        k, stride, strat.strip_rows, strat.n_strips)
    interp = default_interpret()
    kw = dict(kk=k, stride=stride, strip_h=strat.strip_rows,
              quantized=False, interpret=interp)
    if groups == 1:
        ones = jnp.ones((c_out,), jnp.float32)
        out = SK.conv_strip_kernel(xp, wq.astype(jnp.float32), ones, **kw)
    elif cg == 1 and groups == codes.shape[-1] and c_out == groups:
        # plain depthwise (multiplier 1) — the VPU tap-accumulate kernel;
        # channel-multiplier depthwise (c_out = m*groups) falls through to
        # the per-group loop below (each group is a 1-in m-out dense conv)
        ones = jnp.ones((c_out,), jnp.float32)
        out = SK.conv_strip_depthwise_kernel(
            xp, wq.reshape(k * k, c_out).astype(jnp.float32), ones, **kw)
    else:
        og = c_out // groups
        ones = jnp.ones((og,), jnp.float32)
        out = jnp.concatenate([
            SK.conv_strip_kernel(
                xp[..., g * cg:(g + 1) * cg],
                wq[..., g * og:(g + 1) * og].astype(jnp.float32), ones, **kw)
            for g in range(groups)], axis=-1)
    return out[:, :h_out]


def _im2col(codes: jnp.ndarray, k: int, stride: int, pads):
    """[B,H,W,Cin] -> ([B*H'*W', k*k*Cin], H', W').

    Tap order (di, dj, cin) matches ``wq.reshape(k*k*cin, cout)`` so the
    patch @ weight matmul reproduces the conv accumulate exactly.
    """
    (plo, phi), (qlo, qhi) = pads
    xp = jnp.pad(codes, ((0, 0), (plo, phi), (qlo, qhi), (0, 0)))
    h_out = (xp.shape[1] - k) // stride + 1
    w_out = (xp.shape[2] - k) // stride + 1
    cols = []
    for di in range(k):
        for dj in range(k):
            cols.append(xp[:, di:di + (h_out - 1) * stride + 1:stride,
                           dj:dj + (w_out - 1) * stride + 1:stride, :])
    patches = jnp.concatenate(cols, axis=-1)
    return patches.reshape(-1, k * k * codes.shape[-1]), h_out, w_out


def conv_chain(codes: jnp.ndarray, act_scale: jnp.ndarray, stages: Sequence,
               a_qmax, per_frame: bool):
    """Execute one fused conv segment as a single launch: quantized input
    codes -> (codes, act_scale) after the last stage's CRC requant.

    ``stages`` is a sequence of ``(geom, wq, ws, bias)`` tuples — the
    :class:`ChainGeom` static geometry plus the stage's quantized weight
    levels, weight scale and optional bias. Each stage runs the complete
    per-layer recipe (integer tap-loop conv accumulate -> dequant -> bias ->
    activation -> pool -> CRC requant) with expressions matching the
    unfused ``core.plan._execute_steps`` epilogue term for term, so the
    fused output is bit-identical to running the stages as separate steps.

    The inter-stage requant scale is a whole-frame max, which is why the
    caller must guarantee frame-independent calibration: ``per_frame=True``
    (any batch) or batch 1 (where per-tensor and per-frame calibration are
    the same reduction). Returns ``act_scale`` shaped [B, 1, 1, 1] when
    ``per_frame`` else a 0-d scalar — matching the unfused path's scale
    shapes exactly so downstream traced expressions are unchanged.
    """
    if not per_frame and codes.shape[0] != 1:
        raise ValueError(
            "conv_chain: per-tensor calibration fuses only at batch 1 "
            f"(got batch {codes.shape[0]}); the executor should have "
            "fallen back to the unfused path")
    # one tick per conv stage executed through the fused megakernel path
    # (trace time, like dispatch.conv.resident/strip above)
    obs.counter("dispatch.conv.fused").inc(len(stages))
    if get_backend() == "pallas":
        from repro.kernels.conv_bank.fused_kernel import conv_chain_kernel
        out, scale = conv_chain_kernel(codes, act_scale, stages, a_qmax,
                                       interpret=default_interpret())
    else:
        from repro.kernels.conv_bank.ref import conv_chain_ref
        out, scale = conv_chain_ref(codes, act_scale, stages, a_qmax)
    if not per_frame:
        scale = scale[0, 0, 0, 0]          # 0-d, like jnp.max over the tensor
    return out, scale


def ca_acquire(img: jnp.ndarray, pool: int,
               rgb_to_gray: bool | None = None) -> jnp.ndarray:
    """Compressive Acquisitor dispatch. img [B, H, W, C].

    Returns [B, H', W'] (fused gray) or [B, H', W', C] (per-channel pooling),
    matching ``core.compressive.compressive_acquire``. On the pallas backend
    every mode runs the ca_pool kernel; ``dispatch.ca.pallas`` /
    ``dispatch.ca.reference`` count (at trace time, like the conv counters)
    which backend each CA stage was traced for.

    NB: unlike matmul_int/conv_int this is *float* math — the kernel's tap
    summation order differs from the reference einsum by ~1 ulp, so the two
    backends agree only up to downstream CRC requant.
    """
    c = img.shape[-1]
    if rgb_to_gray is None:
        rgb_to_gray = (c == 3)
    if get_backend() == "pallas":
        obs.counter("dispatch.ca.pallas").inc()
        from repro.kernels.ca_pool.ops import ca_pool
        if rgb_to_gray:
            return ca_pool(img, pool=pool, rgb_to_gray=True)
        return ca_pool(img, pool=pool, per_channel=True)
    obs.counter("dispatch.ca.reference").inc()
    from repro.core.compressive import compressive_acquire
    return compressive_acquire(img, pool, rgb_to_gray)
