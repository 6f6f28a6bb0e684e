"""The fused conv-chain Pallas megakernel: one launch per segment.

The per-layer kernels (``kernel.py``, ``strip_kernel.py``) each run one
conv's integer accumulate and hand the epilogue (dequant -> bias ->
activation -> pool -> CRC requant) back to XLA — so an N-stage imaging
chain pays N kernel launches plus N HBM round trips for intermediate
frames. This module executes a whole *fused segment* (a run of chainable
convs picked by ``dispatch.select_fused_segments``) as ONE ``pallas_call``:

  * grid = (batch,): each grid step owns one frame end to end, so the
    input DMA for frame b+1 overlaps frame b's compute via the Pallas
    pipeline emitter (automatic double buffering of the block operands);
  * the stage loop is unrolled in Python at trace time from the segment's
    static ``ChainGeom``s — every stage keeps its intermediate frame in a
    zero-bordered VMEM scratch (the conv's spatial padding), runs the k*k
    tap-loop accumulate over shifted (strided) loads of that scratch
    (exact integers, the same arm-granular structure as the strip kernel),
    then the complete fused epilogue *in-kernel*: dequant, bias (behind
    the ``nextafter`` FMA guard), activation, pooling (strided loads of a
    second scratch), and CRC requantization;
  * the CRC scales travel in SMEM (one scalar per frame in, a broadcast
    lane row per frame out), so no operand has a block narrower than the
    TPU's (8, 128) tile rule admits;
  * the inter-stage CRC scale is a whole-frame max — a stage barrier
    inside the launch. That is deliberate: requant calibration is a global
    reduction, so a halo-grown strip pyramid could only approximate it.
    Whole frames in VMEM keep the math bit-identical to the unfused path,
    which is the correctness bar (``ref.conv_chain_ref`` is the oracle;
    the VMEM budget check in ``dispatch.select_fused_segments`` keeps
    segments inside what this layout can hold).

Because each grid step reduces over its own frame only, the kernel
computes *per-frame* calibration natively; per-tensor calibration fuses
only at batch 1 (the same reduction), which ``dispatch.conv_chain``
enforces.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import ACT_BITS


def _stage_compute(pad_ref, pool_ref, w, ws, b, scale, aq, geom):
    """One fused stage on a single frame held in VMEM.

    ``pad_ref`` [Hp, Wp, C_in] holds the stage input codes inside a zero
    border (the conv's spatial padding); ``pool_ref`` is the [H', W', C_out]
    pooling scratch (None without a pool). w [k, k, C_in/g, C_out];
    ws/b [1, C_out] (b may be None); scale/aq scalars. Returns
    (codes [H', W', C_out], scale'). Every expression mirrors the unfused
    ``plan._execute_steps`` epilogue (and ``ref.conv_chain_ref``) term for
    term — bit-identity depends on it.
    """
    from repro.core.accelerator import _activation
    k, s = geom.kernel, geom.stride
    (plo, phi), (qlo, qhi) = geom.pads
    h_out = (geom.h_in + plo + phi - k) // s + 1
    w_out = (geom.w_in + qlo + qhi - k) // s + 1
    c_in, c_out = pad_ref.shape[-1], w.shape[-1]

    def tap(di, dj):
        if s == 1:
            return pad_ref[pl.ds(di, h_out), pl.ds(dj, w_out), :]
        return pad_ref[pl.ds(di, h_out, stride=s),
                       pl.ds(dj, w_out, stride=s), :]

    if geom.depthwise:
        acc = jnp.zeros((h_out, w_out, c_out), jnp.float32)
        for di in range(k):
            for dj in range(k):
                acc = acc + tap(di, dj) * w[di, dj]
    else:
        acc = jnp.zeros((h_out * w_out, c_out), jnp.float32)
        for di in range(k):
            for dj in range(k):
                pf = tap(di, dj).reshape(h_out * w_out, c_in)
                acc = acc + jax.lax.dot_general(
                    pf, w[di, dj], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        acc = acc.reshape(h_out, w_out, c_out)
    out = acc * (scale * ws)
    if b is not None:
        out = jnp.nextafter(out, out) + b
    y = _activation(out, geom.act)
    if geom.pool is not None:
        # compressive.window_pool's tap order, as strided loads of a scratch
        kind, size = geom.pool
        pool_ref[...] = y
        hp_, wp_ = h_out // size, w_out // size
        y = None
        for i in range(size):
            for j in range(size):
                t = pool_ref[pl.ds(i, hp_, stride=size),
                             pl.ds(j, wp_, stride=size), :]
                y = t if y is None else (
                    jnp.maximum(y, t) if kind == "max" else y + t)
        if kind != "max":
            y = y / (size * size)
    y = jnp.maximum(y, 0.0)
    amax = jnp.max(y)
    new_scale = jnp.maximum(amax, 1e-8) / aq
    codes = jnp.clip(jnp.round(y / new_scale), 0, (1 << ACT_BITS) - 1)
    return codes, new_scale


def _fill_padded(pad_ref, x, geom):
    """Zero ``pad_ref`` and store frame ``x`` inside its conv-padding border."""
    (plo, _), (qlo, _) = geom.pads
    pad_ref[...] = jnp.zeros(pad_ref.shape, pad_ref.dtype)
    pad_ref[pl.ds(plo, geom.h_in), pl.ds(qlo, geom.w_in), :] = x


def _chain_kernel(x_ref, s_ref, aq_ref, *rest, geoms, has_bias):
    """One frame through every fused stage (grid = (batch,)).

    rest = stage operands (w, ws[, b] per stage), then the outputs
    (codes, scale row), then the scratches (pad per stage, then pool per
    pooling stage)."""
    n_in = sum(3 if hb else 2 for hb in has_bias)
    stage_refs = rest[:n_in]
    out_ref, scale_ref = rest[n_in], rest[n_in + 1]
    scratch = list(rest[n_in + 2:])
    pad_refs = scratch[:len(geoms)]
    pool_refs = iter(scratch[len(geoms):])
    x = x_ref[0]
    scale = s_ref[pl.program_id(0)]
    aq = aq_ref[0]
    r = 0
    for i, geom in enumerate(geoms):
        w = stage_refs[r][...]
        ws = stage_refs[r + 1][...]
        r += 2
        b = None
        if has_bias[i]:
            b = stage_refs[r][...]
            r += 1
        _fill_padded(pad_refs[i], x, geom)
        pool_ref = next(pool_refs) if geom.pool is not None else None
        x, scale = _stage_compute(pad_refs[i], pool_ref, w, ws, b, scale, aq,
                                  geom)
    out_ref[0] = x.astype(out_ref.dtype)
    scale_ref[...] = jnp.full(scale_ref.shape, scale, scale_ref.dtype)


def conv_chain_kernel(codes: jnp.ndarray, act_scale, stages: Sequence,
                      a_qmax, interpret: bool = True
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused segment as one ``pallas_call``. codes [B, H, W, C_in].

    ``stages``: sequence of ``(geom: dispatch.ChainGeom, wq, ws, bias)``
    (static geometry + traced operands). ``act_scale`` is the incoming CRC
    scale — 0-d (per-tensor, batch 1) or [B, 1, 1, 1] (per-frame).
    Returns ``(codes [B, H', W', C_out], scale [B, 1, 1, 1])`` after the
    last stage's requant — bit-identical to ``ref.conv_chain_ref``.
    """
    b = codes.shape[0]
    geoms = tuple(g for g, _, _, _ in stages)
    has_bias = tuple(bias is not None for _, _, _, bias in stages)
    s1 = jnp.broadcast_to(jnp.asarray(act_scale, jnp.float32).reshape(-1),
                          (b,))
    aq = jnp.asarray(a_qmax, jnp.float32).reshape(1)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    operands = [codes.astype(jnp.float32), s1, aq]
    in_specs = [
        pl.BlockSpec((1,) + codes.shape[1:], lambda i: (i, 0, 0, 0)),
        smem,
        smem,
    ]

    def _whole(shape):
        nd = len(shape)
        return pl.BlockSpec(shape, lambda i, _nd=nd: (0,) * _nd)

    scratch = []
    pools = []
    for geom, wq, ws, bias in stages:
        c_out = geom.c_out
        wf = wq.astype(jnp.float32)
        operands.append(wf)
        in_specs.append(_whole(wf.shape))
        # per-tensor weight specs give a size-1 ws — broadcast to the
        # channel row the kernel expects (same f32 value, same multiply)
        operands.append(jnp.broadcast_to(
            ws.astype(jnp.float32).reshape(1, -1), (1, c_out)))
        in_specs.append(_whole((1, c_out)))
        if bias is not None:
            operands.append(jnp.asarray(bias, jnp.float32).reshape(1, c_out))
            in_specs.append(_whole((1, c_out)))
        (plo, phi), (qlo, qhi) = geom.pads
        scratch.append(pltpu.VMEM((geom.h_in + plo + phi,
                                   geom.w_in + qlo + qhi, geom.c_in),
                                  jnp.float32))
        if geom.pool is not None:
            h_conv = (geom.h_in + plo + phi - geom.kernel) // geom.stride + 1
            w_conv = (geom.w_in + qlo + qhi - geom.kernel) // geom.stride + 1
            pools.append(pltpu.VMEM((h_conv, w_conv, c_out), jnp.float32))

    h_out, w_out = geoms[-1].out_hw()
    c_out = geoms[-1].c_out
    lanes = 128        # the out scale is one lane row per frame: a (1, 1)
                       # block would break the (8, 128) block rule

    out, scale = pl.pallas_call(
        functools.partial(_chain_kernel, geoms=geoms, has_bias=has_bias),
        grid=(b,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, h_out, w_out, c_out), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, 1, lanes), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h_out, w_out, c_out), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, lanes), jnp.float32),
        ],
        scratch_shapes=scratch + pools,
        interpret=interpret,
    )(*operands)
    return out, scale[:, :, :1].reshape(b, 1, 1, 1)
