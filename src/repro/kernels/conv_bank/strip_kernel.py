"""Strip-mined Pallas conv kernels for frames past the VMEM-resident budget.

``kernel.py`` maps the whole SAME-padded image as one VMEM block — right for
the paper's <=32x32 evaluation models, wrong for full sensor frames and the
VGG16/AlexNet layers of Fig. 10 where the image (let alone its im2col patch
matrix) no longer fits on-chip. This module is the large-frame path:

  * the output spatial rows are tiled into strips of ``strip_h`` rows;
  * the input stays off-chip (``memory_space=ANY``) and each strip's input
    rows plus (k-1)-row halo are DMA'd into a VMEM scratch slot
    (``pltpu.make_async_copy``) — fetched once per strip and reused across
    every output-channel block;
  * the channel (lane) axis is zero-padded to a multiple of 128 before the
    kernel: the TPU lays VMEM out in (8, 128) tiles, and a DMA or slice of
    a scratch whose lane extent is not tile-aligned is refused by the
    compiler. Zero input channels (and zero weight rows) add exact zeros,
    so the padding cannot change the accumulate. ``vmem_row_bytes`` is the
    one place that prices a scratch row as the compiler lays it out;
  * the halo DMA is **double-buffered**: the scratch holds two strip slots
    with a DMA semaphore each, and while strip s's tap loop computes out of
    slot s%2, the DMA for strip s+1 is already in flight into the other
    slot — the copy latency hides behind the k*k matmul loop instead of
    serializing in front of it (the strip for s=0 is the only cold fetch).
  * the tap loop then runs unchanged on the VMEM strip: k*k shifted
    [strip_h*W, C_in] x [C_in, bn] MXU matmuls accumulated in f32, the same
    arm-granular structure as the resident kernel, so the integer-exactness
    envelope (|sum| < 2^24) is identical.

Grid: (batch, strip, out-channel block) — the channel block innermost so one
halo DMA serves ``C_out / bn`` compute steps (input-stationary).

On the quantized path the kernels can also fuse the per-layer epilogue
(dequant -> bias -> activation) behind the accumulate via ``act=`` /
``bias=`` — the expressions mirror ``core.plan._execute_steps`` (including
the ``nextafter`` FMA guard), so the fused epilogue stays bit-identical to
the separate XLA ops it replaces. The CRC *requant* cannot fuse here: its
scale is a whole-frame max and a strip only sees its own rows — whole-frame
requant fusion lives in ``fused_kernel.conv_chain_kernel``.

The depthwise variant keeps the strip/halo structure but replaces the MXU
matmul with a VPU multiply-accumulate per tap (each output channel sees one
input channel), eliminating the per-channel im2col the grouped resident path
used to do. Strategy selection / geometry lives in ``kernels.dispatch``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128          # TPU vreg lane count: last-dim tile of a VMEM buffer
SUBLANES = 8         # f32 sublane count: second-to-last-dim tile


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def vmem_row_bytes(width: int, channels: int) -> int:
    """Bytes one [width, channels] f32 row occupies in VMEM: the compiler
    pads the last dim to 128 lanes and the second-to-last to 8 sublanes."""
    return round_up(width, SUBLANES) * round_up(channels, LANES) * 4


def _pad_lanes(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Zero-pad ``axis`` up to a multiple of 128 lanes (no-op if aligned)."""
    extra = round_up(x.shape[axis], LANES) - x.shape[axis]
    if not extra:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, extra)
    return jnp.pad(x, pads)


def _pad_tiles(x: jnp.ndarray) -> jnp.ndarray:
    """[B, Hp, Wp, C] -> zero columns up to a multiple of 8 sublanes and zero
    channels up to a multiple of 128 lanes, so every DMA and slice of the
    strip scratch is tile-aligned. Output width is computed from the real
    ``Wp`` first; the extra columns are never read by a tap."""
    extra = round_up(x.shape[2], SUBLANES) - x.shape[2]
    if extra:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, extra), (0, 0)))
    return _pad_lanes(x, 3)


def _out_block(c_out: int, bn: int) -> int:
    """Out-channel block: a multiple of 128 that divides ``c_out`` (at most
    ``bn`` when that allows), else all of ``c_out`` — the only block widths
    the TPU's (8, 128) block rule admits on the lane axis."""
    if c_out % LANES:
        return c_out
    bn = max(LANES, bn - bn % LANES)
    while c_out % bn:
        bn -= LANES
    return bn


def pad_rows_for_strips(xp: jnp.ndarray, kk: int, stride: int,
                        strip_rows: int, n_strips: int) -> jnp.ndarray:
    """Zero-pad the bottom rows of a spatially-padded input so ``n_strips``
    strips of ``strip_rows`` output rows tile exactly (the kernels' geometry
    contract). The single home of the row-padding recipe for every caller
    (dispatch strip path, ops wrapper): the padded height is
    ``(n_strips*strip_rows - 1)*stride + kk``. When the input already has
    surplus trailing rows (strided VALID convs drop up to stride-1 rows),
    nothing is added — the kernels' floor division ignores the surplus."""
    extra = (n_strips * strip_rows - 1) * stride + kk - xp.shape[1]
    if extra <= 0:
        return xp
    return jnp.pad(xp, ((0, 0), (0, extra), (0, 0), (0, 0)))


def _tap_patch(xs_ref, slot, di: int, dj: int, strip_h: int, w_out: int,
               stride: int) -> jnp.ndarray:
    """The (di, dj) tap's window of VMEM strip ``slot`` -> [strip_h, w_out, c].

    A strided load from the scratch ref (rows are the major axis, columns
    the sublane axis; the lane axis is never strided), not a strided slice
    of a loaded value, which the TPU compiler does not lower."""
    if stride == 1:
        return xs_ref[slot, pl.ds(di, strip_h), pl.ds(dj, w_out), :]
    return xs_ref[slot, pl.ds(di, strip_h, stride=stride),
                  pl.ds(dj, w_out, stride=stride), :]


def _epilogue(acc: jnp.ndarray, act_scale: float, ws, b, act: str):
    """The fused quantized epilogue: dequant -> bias -> activation.

    Expression-for-expression the unfused ``plan._execute_steps`` recipe
    (``nextafter(x, x)`` is its FMA guard) so fusing it into the kernel
    cannot change a bit.
    """
    acc = acc * act_scale * ws
    if b is not None:
        acc = jnp.nextafter(acc, acc) + b
    if act != "none":
        from repro.core.accelerator import _activation
        acc = _activation(acc, act)
    return acc


def _strip_dma(x_hbm, xs_ref, sems, b, s, *, stride: int, strip_h: int,
               rows_in: int, n_strips: int):
    """Double-buffered halo DMA for strip ``s`` of batch ``b``.

    Waits for slot s%2 (strip s's rows + halo, started by the previous
    strip's prefetch — or right here for the cold first strip of a batch),
    then starts the DMA for strip s+1 into the other slot so it lands
    while the caller's tap loop runs. Returns the ready slot index.
    """
    def _copy(strip, slot):
        return pltpu.make_async_copy(
            x_hbm.at[b, pl.ds(strip * (strip_h * stride), rows_in)],
            xs_ref.at[slot], sems.at[slot])

    slot = jax.lax.rem(s, 2)

    @pl.when(s == 0)
    def _cold_fetch():
        _copy(0, 0).start()

    _copy(s, slot).wait()

    @pl.when(s + 1 < n_strips)
    def _prefetch_next():
        _copy(s + 1, jax.lax.rem(s + 1, 2)).start()

    return slot


def _conv_strip_kernel(x_hbm, w_ref, ws_ref, *rest, kk: int, stride: int,
                       strip_h: int, w_out: int, c_in: int, rows_in: int,
                       n_strips: int, act_scale: float, quantized: bool,
                       act: str, has_bias: bool):
    """One (strip, out-channel block) output tile.

    x_hbm:  [B, Hp, Wp, c_in] in ANY/HBM — never blocked into VMEM whole
            (c_in lane-padded to a multiple of 128)
    w_ref:  [kk, kk, c_in, bn] VMEM        ws_ref: [1, bn]
    xs_ref: [2, rows_in, Wp, c_in] VMEM scratch (two strip+halo slots,
            double-buffered; persists across the innermost grid dim);
    sems:   one DMA completion semaphore per slot
    out_ref: [1, strip_h, w_out, bn]
    """
    b_ref = rest[0] if has_bias else None
    out_ref, xs_ref, sems = rest[-3], rest[-2], rest[-1]
    b = pl.program_id(0)
    s = pl.program_id(1)
    n_blk = pl.program_id(2)

    @pl.when(n_blk == 0)
    def _fetch_strip():
        _strip_dma(x_hbm, xs_ref, sems, b, s, stride=stride, strip_h=strip_h,
                   rows_in=rows_in, n_strips=n_strips)

    slot = jax.lax.rem(s, 2)
    bn = out_ref.shape[-1]
    acc = jnp.zeros((strip_h * w_out, bn), jnp.float32)
    for di in range(kk):
        for dj in range(kk):
            patch = _tap_patch(xs_ref, slot, di, dj, strip_h, w_out, stride)
            pf = patch.reshape(strip_h * w_out, c_in).astype(jnp.float32)
            wf = w_ref[di, dj].astype(jnp.float32)       # [c_in, bn]
            acc = acc + jax.lax.dot_general(
                pf, wf, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    if quantized:
        acc = _epilogue(acc, act_scale, ws_ref[...],
                        b_ref[...] if has_bias else None, act)
    out_ref[0] = acc.reshape(strip_h, w_out, bn).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kk", "stride", "strip_h", "bn",
                                             "act_scale", "quantized", "act",
                                             "interpret"))
def conv_strip_kernel(x_padded: jnp.ndarray, w: jnp.ndarray, ws: jnp.ndarray,
                      kk: int, stride: int = 1, strip_h: int = 8,
                      bn: int = 128, act_scale: float = 1.0,
                      quantized: bool = False, act: str = "none",
                      bias: jnp.ndarray | None = None,
                      interpret: bool = True) -> jnp.ndarray:
    """x_padded [B, Hp, Wp, Cin]; w [kk,kk,Cin,Cout] -> [B, H_out, W_out, Cout].

    Geometry contract (enforced): the caller pads the rows so the strips
    tile exactly — ``Hp == (n_strips*strip_h - 1)*stride + kk`` — i.e. the
    last strip's halo DMA ends exactly at the padded bottom edge. Output
    rows past the true h_out are the caller's padding to slice off.

    On the quantized path ``act``/``bias`` fuse the per-layer epilogue
    (dequant -> bias -> activation) into the kernel — see ``_epilogue``.
    ``bn`` bounds the out-channel block (see ``_out_block``).
    """
    b, hp, wp, _ = x_padded.shape
    w_out = (wp - kk) // stride + 1
    n_rows = (hp - kk) // stride + 1
    if strip_h < 1:
        raise ValueError(f"conv_strip_kernel: strip_h={strip_h} must be >= 1 "
                         f"(use dispatch.select_conv_strategy for geometry)")
    if n_rows % strip_h:
        raise ValueError(
            f"conv_strip_kernel: padded rows {hp} give {n_rows} output rows, "
            f"not a multiple of strip_h={strip_h}")
    n_strips = n_rows // strip_h
    rows_in = (strip_h - 1) * stride + kk
    c_out = w.shape[-1]
    bn = _out_block(c_out, bn)
    x_padded = _pad_tiles(x_padded.astype(jnp.float32))
    w = _pad_lanes(w.astype(jnp.float32), 2)
    wp, c_in = x_padded.shape[2:]
    ws2 = ws.reshape(1, c_out).astype(jnp.float32)
    has_bias = bias is not None
    operands = [x_padded, w, ws2]
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((kk, kk, c_in, bn), lambda i, s, n: (0, 0, 0, n)),
        pl.BlockSpec((1, bn), lambda i, s, n: (0, n)),
    ]
    if has_bias:
        operands.append(jnp.asarray(bias, jnp.float32).reshape(1, c_out))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, s, n: (0, n)))
    return pl.pallas_call(
        functools.partial(_conv_strip_kernel, kk=kk, stride=stride,
                          strip_h=strip_h, w_out=w_out, c_in=c_in,
                          rows_in=rows_in, n_strips=n_strips,
                          act_scale=act_scale, quantized=quantized,
                          act=act, has_bias=has_bias),
        grid=(b, n_strips, c_out // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, strip_h, w_out, bn),
                               lambda i, s, n: (i, s, 0, n)),
        out_shape=jax.ShapeDtypeStruct((b, n_rows, w_out, c_out),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, rows_in, wp, c_in), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(*operands)


def _conv_strip_dw_kernel(x_hbm, w_ref, ws_ref, *rest, kk: int, stride: int,
                          strip_h: int, w_out: int, c: int, rows_in: int,
                          n_strips: int, act_scale: float, quantized: bool,
                          act: str, has_bias: bool):
    """Depthwise strip: every channel convolves with its own kk x kk filter.

    w_ref: [kk*kk, c] (tap-major) — the tap loop is a VPU multiply-accumulate
    over all channels at once; no im2col, no per-channel kernel launches.
    Same double-buffered halo DMA as the dense strip kernel.
    """
    b_ref = rest[0] if has_bias else None
    out_ref, xs_ref, sems = rest[-3], rest[-2], rest[-1]
    b = pl.program_id(0)
    s = pl.program_id(1)
    slot = _strip_dma(x_hbm, xs_ref, sems, b, s, stride=stride,
                      strip_h=strip_h, rows_in=rows_in, n_strips=n_strips)

    acc = jnp.zeros((strip_h, w_out, c), jnp.float32)
    for di in range(kk):
        for dj in range(kk):
            patch = _tap_patch(xs_ref, slot, di, dj, strip_h, w_out, stride)
            acc = acc + patch.astype(jnp.float32) * w_ref[pl.ds(di * kk + dj, 1), :]
    if quantized:
        acc = _epilogue(acc, act_scale, ws_ref[...],
                        b_ref[...] if has_bias else None, act)
    out_ref[0] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kk", "stride", "strip_h",
                                             "act_scale", "quantized", "act",
                                             "interpret"))
def conv_strip_depthwise_kernel(x_padded: jnp.ndarray, w_taps: jnp.ndarray,
                                ws: jnp.ndarray, kk: int, stride: int = 1,
                                strip_h: int = 8, act_scale: float = 1.0,
                                quantized: bool = False, act: str = "none",
                                bias: jnp.ndarray | None = None,
                                interpret: bool = True) -> jnp.ndarray:
    """x_padded [B, Hp, Wp, C]; w_taps [kk*kk, C] -> [B, H_out, W_out, C].

    Same row-padding contract as :func:`conv_strip_kernel`; channels are
    lane-padded inside and sliced back off the output.
    """
    b, hp, wp, c_real = x_padded.shape
    w_out = (wp - kk) // stride + 1
    n_rows = (hp - kk) // stride + 1
    if strip_h < 1:
        raise ValueError(f"conv_strip_depthwise_kernel: strip_h={strip_h} "
                         f"must be >= 1")
    if n_rows % strip_h:
        raise ValueError(
            f"conv_strip_depthwise_kernel: padded rows {hp} give {n_rows} "
            f"output rows, not a multiple of strip_h={strip_h}")
    n_strips = n_rows // strip_h
    rows_in = (strip_h - 1) * stride + kk
    x_padded = _pad_tiles(x_padded.astype(jnp.float32))
    wp, c = x_padded.shape[2:]
    ws2 = _pad_lanes(ws.reshape(1, c_real).astype(jnp.float32), 1)
    has_bias = bias is not None
    operands = [x_padded, _pad_lanes(w_taps.astype(jnp.float32), 1), ws2]
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((kk * kk, c), lambda i, s: (0, 0)),
        pl.BlockSpec((1, c), lambda i, s: (0, 0)),
    ]
    if has_bias:
        operands.append(_pad_lanes(
            jnp.asarray(bias, jnp.float32).reshape(1, c_real), 1))
        in_specs.append(pl.BlockSpec((1, c), lambda i, s: (0, 0)))
    return pl.pallas_call(
        functools.partial(_conv_strip_dw_kernel, kk=kk, stride=stride,
                          strip_h=strip_h, w_out=w_out, c=c, rows_in=rows_in,
                          n_strips=n_strips, act_scale=act_scale,
                          quantized=quantized, act=act, has_bias=has_bias),
        grid=(b, n_strips),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, strip_h, w_out, c),
                               lambda i, s: (i, s, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_rows, w_out, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, rows_in, wp, c), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(*operands)[..., :c_real]
