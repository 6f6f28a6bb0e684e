"""Pallas kernel for the Compressive Acquisitor (paper Sec. 3.2, eq. (1)).

One CA bank computes, in a single optical cycle per output pixel group,
    P_out[i,j] = sum_{di,dj,c} coeff[di,dj,c] * P_in[p*i+di, p*j+dj, c]
with pre-set coefficients (RGB->gray x mean-pool). On TPU this is a fused
weighted reduction over tap *planes*: the wrapper regroups the frame once
([B, H, W, C] -> [B, p*p*C, H/p, W/p], one XLA transpose) so that every
tap (di, dj, c) is a dense [H/p, W/p] plane with W on the 128-lane axis.
The kernel then reads each plane once and multiply-accumulates it on the
VPU with its coefficient, a scalar held in SMEM — no strided access inside
the kernel (the TPU compiler lowers no strided slice of a vector), and no
intermediate grayscale or pooled tensor in HBM.

Grid: (B, H_out / th). The p*p*C tap loop is static (<= 48 taps for p=4,
C=3), unrolled — the TPU analogue of the CA bank's parallel wavelength
taps. ``per_channel`` keeps one output per input channel (pooling without
the RGB->gray mix) instead of summing every tap into one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM the double-buffered tap-plane block may claim per grid step
_BLOCK_BUDGET = 4 << 20


def _ca_kernel(x_ref, coef_ref, out_ref, *, taps: int, c: int,
               per_channel: bool):
    """x_ref: [1, taps, th, w_out] tap planes, tap-major (di, dj, c);
    coef_ref: [taps] f32 in SMEM; out_ref: [1, c_out, th, w_out]."""
    for o in range(out_ref.shape[1]):
        acc = None
        for t in range(taps):
            if per_channel and t % c != o:
                continue
            term = x_ref[0, t].astype(jnp.float32) * coef_ref[t]
            acc = term if acc is None else acc + term
        out_ref[0, o] = acc.astype(out_ref.dtype)


def _rows_per_step(h_out: int, row_bytes: int, sublanes: int) -> int:
    """Output rows per grid step: all of them when the block fits the
    budget, else the largest sublane-aligned divisor of ``h_out`` that does
    (the (8, 128) block rule: aligned or the full extent)."""
    if 2 * h_out * row_bytes <= _BLOCK_BUDGET:
        return h_out
    th = (_BLOCK_BUDGET // (2 * row_bytes)) // sublanes * sublanes
    while th >= sublanes:
        if h_out % th == 0:
            return th
        th -= sublanes
    return h_out


@functools.partial(jax.jit, static_argnames=("pool", "per_channel",
                                             "interpret"))
def ca_pool_kernel(img: jnp.ndarray, coeffs: jnp.ndarray, pool: int = 2,
                   per_channel: bool = False,
                   interpret: bool = True) -> jnp.ndarray:
    """img [B, H, W, C] -> [B, H/pool, W/pool] fused weighted acquisition,
    or [B, H/pool, W/pool, C] with ``per_channel``."""
    b, h, w, c = img.shape
    if h % pool or w % pool:
        raise ValueError(f"H({h}), W({w}) must be divisible by pool={pool}")
    h_out, w_out = h // pool, w // pool
    taps = pool * pool * c
    planes = img.reshape(b, h_out, pool, w_out, pool, c) \
        .transpose(0, 2, 4, 5, 1, 3).reshape(b, taps, h_out, w_out)
    c_out = c if per_channel else 1
    itemsize = jnp.dtype(img.dtype).itemsize
    lanes = -(-w_out // 128) * 128
    th = _rows_per_step(h_out, taps * lanes * itemsize, 8 * (4 // itemsize))
    out = pl.pallas_call(
        functools.partial(_ca_kernel, taps=taps, c=c,
                          per_channel=per_channel),
        grid=(b, h_out // th),
        in_specs=[
            pl.BlockSpec((1, taps, th, w_out), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, c_out, th, w_out),
                               lambda i, j: (i, 0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c_out, h_out, w_out), img.dtype),
        interpret=interpret,
    )(planes, coeffs.astype(jnp.float32).reshape(taps))
    return out.transpose(0, 2, 3, 1) if per_channel else out[:, 0]
