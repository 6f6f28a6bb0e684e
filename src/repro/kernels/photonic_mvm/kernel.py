"""Pallas TPU kernel for the Optical Core's quantized MVM.

The hardware being emulated (paper Secs. 3-4): activations arrive as uint4
CRC codes on VCSEL wavelengths; weights sit on MRs as signed w-bit integers;
each arm computes a 9-tap integer dot (BPD accumulate), the summation tree
adds arm partials, and the electronic back-end applies the dequant scales.

TPU adaptation (DESIGN.md §2): the 9-MR arm becomes the 128-lane MXU row;
one OC weight mapping becomes one VMEM-resident weight tile. Integer MACs
run on the MXU via int8 carriers with ``preferred_element_type=int32`` —
bit-exact with the photonic integer math. The K-block loop in the grid IS
the summation tree: partial sums accumulate in an int32 VMEM scratch
across K steps (stage-1/stage-2 adds), and the final step applies
``act_scale * w_scale[col]`` (the transmitter's dequant) and writes bf16/f32.

Grid: (M/bm, N/bn, K/bk), K innermost (sequential accumulation). Weight
blocks only change with (n, k) — Pallas keeps the block resident in VMEM
across the M loop, exactly the weight-stationary reuse the paper's DMVA
enables.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _mvm_kernel(a_ref, w_ref, ws_ref, out_ref, acc_ref, *, n_k: int,
                act_scale: float):
    """One (bm, bn) output tile; accumulates over the K grid dimension.

    a_ref:  [bm, bk] int8  — CRC activation codes (0..15)
    w_ref:  [bk, bn] int8  — MR weight levels (signed, |q| <= 7)
    ws_ref: [1, bn] f32    — per-output-channel weight scales
    acc_ref:[bm, bn] int32 — summation-tree accumulator (VMEM scratch)
    """
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # arm dots + BPD accumulate: integer MAC on the MXU
    a = a_ref[...]
    w = w_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        a, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k_idx == n_k - 1)
    def _dequant():
        # transmitter: dequantize with act & per-channel weight scales
        out_ref[...] = (acc_ref[...].astype(jnp.float32)
                        * act_scale * ws_ref[...]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "act_scale",
                                             "out_dtype", "interpret"))
def mvm_int_kernel(a_codes: jnp.ndarray, wq: jnp.ndarray, ws: jnp.ndarray,
                   act_scale: float = 1.0, bm: int = DEFAULT_BM,
                   bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                   out_dtype=jnp.float32, interpret: bool = True):
    """a_codes [M,K] int8, wq [K,N] int8, ws [N] f32 -> [M,N] out_dtype.

    M, K, N are padded to block multiples by the caller (ops.py).
    """
    m, k = a_codes.shape
    _, n = wq.shape
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    ws2 = ws.reshape(1, n).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_mvm_kernel, n_k=n_k, act_scale=act_scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="mvm_int_kernel",
    )(a_codes, wq, ws2)
