"""jit'd public wrapper for the photonic MVM kernel.

Handles: float->code quantization (CRC + MR imprinting), signed activations
via the two-rail BPD trick (sign * |code|), block padding, leading dims,
and the interpret switch (``dispatch.default_interpret``: True on CPU, False
on a TPU, where the kernel compiles through Mosaic — ``tests/test_tpu_compile``
compiles it for a described v5e, ``chip_smoke.py`` runs it on one).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.quant import WASpec, quantize_weight
from repro.kernels.dispatch import default_interpret
from repro.kernels.photonic_mvm import kernel as K


def _pad_to(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    size = x.shape[axis]
    rem = size % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad)


def photonic_mvm_prequant(a_signed_codes: jnp.ndarray, wq: jnp.ndarray,
                          ws: jnp.ndarray, act_scale: float = 1.0,
                          bm: int = K.DEFAULT_BM, bn: int = K.DEFAULT_BN,
                          bk: int = K.DEFAULT_BK,
                          out_dtype=jnp.float32) -> jnp.ndarray:
    """Already-quantized operands (int8 carriers) -> dequantized output.

    a_signed_codes: [..., K] int8 in [-15, 15]; wq: [K, N] int8; ws: [N].
    """
    *lead, kdim = a_signed_codes.shape
    n = wq.shape[-1]
    a2 = a_signed_codes.reshape(-1, kdim)
    m = a2.shape[0]
    a2 = _pad_to(_pad_to(a2, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(wq, bk, 0), bn, 1)
    wsp = _pad_to(ws.reshape(-1), bn, 0)
    out = K.mvm_int_kernel(a2, wp, wsp, act_scale=act_scale, bm=bm, bn=bn,
                           bk=bk, out_dtype=out_dtype,
                           interpret=default_interpret())
    return out[:m, :n].reshape(*lead, n)


def photonic_mvm(x: jnp.ndarray, w: jnp.ndarray, spec: WASpec,
                 act_scale: float = 1.0 / 15.0,
                 bm: int = K.DEFAULT_BM, bn: int = K.DEFAULT_BN,
                 bk: int = K.DEFAULT_BK) -> jnp.ndarray:
    """Float API: x [..., K] @ w [K, N] under [W:A] ``spec``.

    Quantizes both operands the way the sensor/OC would, then runs the
    integer kernel. Matches ref.photonic_mvm_ref bit-exactly.
    """
    *lead, kdim = x.shape
    xf = x.reshape(-1, kdim).astype(jnp.float32)
    sgn = jnp.sign(xf)
    codes = jnp.clip(jnp.round(jnp.abs(xf) / act_scale), 0, spec.a_qmax)
    a = (sgn * codes).astype(jnp.int8)
    wq, ws = quantize_weight(w.astype(jnp.float32), spec, axis=-1)
    y = photonic_mvm_prequant(a, wq, ws.reshape(-1), act_scale=act_scale,
                              bm=bm, bn=bn, bk=bk)
    return y.reshape(*lead, w.shape[-1]).astype(x.dtype)
