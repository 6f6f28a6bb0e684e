"""Oracles for conv_bank: XLA's conv_general_dilated on the same operands,
plus the fused-chain reference (``conv_chain_ref``) — the bit-exact oracle
for the megakernel path (``fused_kernel.conv_chain_kernel``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quant import ACT_BITS, WASpec, quantize_weight


def conv_bank_ref(x: jnp.ndarray, w: jnp.ndarray, padding: str = "SAME"
                  ) -> jnp.ndarray:
    """Float conv oracle. x [B,H,W,Cin]; w [k,k,Cin,Cout]."""
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32), (1, 1), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def conv_bank_quant_ref(x: jnp.ndarray, w: jnp.ndarray, spec: WASpec,
                        act_scale: float = 1.0 / 15.0,
                        padding: str = "SAME") -> jnp.ndarray:
    """Quantized conv oracle — the LightatorDevice integer semantics."""
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / act_scale), 0,
                     spec.a_qmax)
    wq, ws = quantize_weight(w.astype(jnp.float32), spec, axis=-1)
    acc = jax.lax.conv_general_dilated(
        codes, wq.astype(jnp.float32), (1, 1), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return acc * act_scale * ws.reshape(1, 1, 1, -1)


# ---------------------------------------------------------------------------
# Fused chain reference
# ---------------------------------------------------------------------------

def conv_taps_int(x: jnp.ndarray, wq: jnp.ndarray, kernel: int, stride: int,
                  pads, depthwise: bool = False) -> jnp.ndarray:
    """Integer-exact conv accumulate as a k*k tap loop of shifted windows.

    Bit-identical to ``lax.conv_general_dilated`` on the same quantized
    operands: every partial product is an exact small integer carried in
    f32 (|sum| < 2^24), so the summation order cannot matter. The tap-loop
    formulation is what the fused megakernel runs per stage — and on CPU it
    is also dramatically faster than the general conv lowering for the
    small channel counts the fusion heuristic admits.
    """
    k, s = kernel, stride
    (plo, phi), (qlo, qhi) = pads
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (plo, phi), (qlo, qhi),
                                         (0, 0)))
    b, hp, wp, c_in = xp.shape
    h_out = (hp - k) // s + 1
    w_out = (wp - k) // s + 1
    wf = wq.astype(jnp.float32)
    c_out = wf.shape[-1]
    acc = jnp.zeros((b, h_out, w_out, c_out), jnp.float32)
    for di in range(k):
        for dj in range(k):
            patch = jax.lax.slice(
                xp, (0, di, dj, 0),
                (b, di + (h_out - 1) * s + 1, dj + (w_out - 1) * s + 1, c_in),
                (1, s, s, 1))
            if depthwise:
                acc = acc + patch * wf[di, dj, 0]
            else:
                acc = acc + jnp.einsum("bhwc,cn->bhwn", patch, wf[di, dj])
    return acc


def conv_chain_ref(codes: jnp.ndarray, act_scale, stages, a_qmax):
    """The fused conv-chain oracle: whole frames through every stage inside
    one traced computation, epilogue expressions matching the unfused
    ``core.plan._execute_steps`` term for term.

    ``stages``: sequence of ``(geom: dispatch.ChainGeom, wq, ws, bias)``.
    Returns ``(codes, act_scale)`` after the last stage's CRC requant, with
    the scale reduced per frame ([B, 1, 1, 1]) — at batch 1 the same
    reduction as per-tensor calibration, bit for bit.
    """
    from repro.core.accelerator import _activation
    from repro.core.compressive import window_pool
    x, scale = codes, act_scale
    for geom, wq, ws, bias in stages:
        acc = conv_taps_int(x, wq, geom.kernel, geom.stride, geom.pads,
                            depthwise=geom.depthwise)
        out = acc * (scale * ws.reshape(1, 1, 1, -1))
        if bias is not None:
            # nextafter(x, x): the unfused path's exact-identity FMA guard
            out = jnp.nextafter(out, out) + bias
        y = _activation(out, geom.act)
        if geom.pool is not None:
            y = window_pool(y, *geom.pool)
        y = jnp.maximum(y, 0.0)
        amax = jnp.max(y, axis=(1, 2, 3), keepdims=True)
        scale = jnp.maximum(amax, 1e-8) / a_qmax
        x = jnp.clip(jnp.round(y / scale), 0, (1 << ACT_BITS) - 1)
    return x, scale
