"""Pallas kernel for the All-in-One Convolver's conv mapping (paper Fig. 6).

The OC computes a kxk conv as k*k tap-position dot products accumulated by
the BPD + summation tree. The TPU translation keeps that structure: each of
the k*k taps is a shifted [H*W, C_in] x [C_in, bn] MXU matmul, accumulated
in f32 — the tap loop is static (9/25/49, the paper's arm-granular
segmentation), and each grid step emits the output tile for one block of
output channels (one "round" of mapped kernels, exactly the weight-remap
round of core.optical_core.schedule_conv).

Quantized variant: int8 carriers (uint4 CRC codes x signed w-bit MR levels),
integer-exact accumulation in f32 (|sum| < 2^24), dequant at the end —
matching LightatorDevice's conv semantics. The per-layer epilogue
(dequant -> bias -> activation) can fuse behind the accumulate via
``act=`` / ``bias=`` with the same bit-identity guarantee as the strip
kernels (shared ``strip_kernel._epilogue`` expressions).

Grid: (B, C_out / bn); the SAME-padded input image is one VMEM block
(the paper's models are <= 32x32 — a 64x64x256 f32 strip is ~4 MB; larger
frames would move to a strip-mined variant with halo DMA).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.conv_bank.strip_kernel import _epilogue


def _conv_kernel(x_ref, w_ref, ws_ref, *rest, kk: int, h_out: int,
                 w_out: int, c_in: int, act_scale: float, quantized: bool,
                 act: str, has_bias: bool):
    """x_ref: [1, H+k-1, W+k-1, c_in]; w_ref: [k, k, c_in, bn];
    ws_ref: [1, bn]; out_ref: [1, H, W, bn]."""
    b_ref = rest[0] if has_bias else None
    out_ref = rest[-1]
    x = x_ref[0]
    bn = out_ref.shape[-1]
    acc = jnp.zeros((h_out * w_out, bn), jnp.float32)
    for di in range(kk):
        for dj in range(kk):
            patch = jax.lax.slice(
                x, (di, dj, 0), (di + h_out, dj + w_out, c_in))
            pf = patch.reshape(h_out * w_out, c_in).astype(jnp.float32)
            wf = w_ref[di, dj].astype(jnp.float32)       # [c_in, bn]
            acc = acc + jax.lax.dot_general(
                pf, wf, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    if quantized:
        acc = _epilogue(acc, act_scale, ws_ref[...],
                        b_ref[...] if has_bias else None, act)
    out_ref[0] = acc.reshape(h_out, w_out, bn).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kk", "bn", "act_scale",
                                             "quantized", "act", "interpret"))
def conv_bank_kernel(x_padded: jnp.ndarray, w: jnp.ndarray, ws: jnp.ndarray,
                     kk: int = 3, bn: int = 64,
                     act_scale: float = 1.0, quantized: bool = False,
                     act: str = "none", bias: jnp.ndarray | None = None,
                     interpret: bool = True) -> jnp.ndarray:
    """x_padded [B, H+k-1, W+k-1, Cin]; w [k,k,Cin,Cout] -> [B, H, W, Cout]."""
    b, hp, wp, c_in = x_padded.shape
    h_out, w_out = hp - kk + 1, wp - kk + 1
    c_out = w.shape[-1]
    bn = min(bn, c_out)
    while c_out % bn:
        bn -= 1
    grid = (b, c_out // bn)
    ws2 = ws.reshape(1, c_out).astype(jnp.float32)
    has_bias = bias is not None
    operands = [x_padded, w, ws2]
    in_specs = [
        pl.BlockSpec((1, hp, wp, c_in), lambda i, n: (i, 0, 0, 0)),
        pl.BlockSpec((kk, kk, c_in, bn), lambda i, n: (0, 0, 0, n)),
        pl.BlockSpec((1, bn), lambda i, n: (0, n)),
    ]
    if has_bias:
        operands.append(jnp.asarray(bias, jnp.float32).reshape(1, c_out))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, n: (0, n)))
    return pl.pallas_call(
        functools.partial(_conv_kernel, kk=kk, h_out=h_out, w_out=w_out,
                          c_in=c_in, act_scale=act_scale, quantized=quantized,
                          act=act, has_bias=has_bias),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h_out, w_out, bn),
                               lambda i, n: (i, 0, 0, n)),
        out_shape=jax.ShapeDtypeStruct((b, h_out, w_out, c_out), jnp.float32),
        interpret=interpret,
        name="conv_bank_kernel",
    )(*operands)
