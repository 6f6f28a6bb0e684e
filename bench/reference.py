"""Plain reference of a configuration's forward pass, as served.

Written from the configuration file alone, in straightforward ``jax.numpy``;
it imports nothing of the program under test. The semantics are the
Lightator device's with per-frame calibration, the hardware's
frame-per-pass mode that the server runs:

- CRC requant: ``x = max(x, 0)``; ``scale = max(max_frame(x), 1e-8) / 15``;
  ``codes = clip(round(x / scale), 0, 15)``, the max taken over each frame.
- Compressive acquisition: intensities ``codes * scale`` summed over the
  ``pool x pool`` window and the three channels with weights
  ``(0.299, 0.587, 0.114) / pool**2``, taps in (row, column, channel)
  order, then requant.
- Conv / dense: weights quantized per output channel,
  ``s = max(|w|, 1e-8) / w_qmax``, ``q = clip(round(w / s), -w_qmax,
  w_qmax)``; the accumulate of codes by levels is an exact integer sum
  (bfloat16 carries both exactly, the product accumulates in float32, and
  every sum stays under 2**24); dequant ``acc * (act_scale * s) + b``;
  ReLU; 2x2 max pool; requant. The last dense layer's dequantized output
  is the logits.

The divisors 15 and ``w_qmax`` enter as traced arguments, so the compiler
cannot turn ``x / 15`` into ``x * (1 / 15)``, which rounds differently.

``dtype`` selects the precision of every float step. float32 is the
configuration's; bfloat16 is the control, the nearest precision below,
which the comparison has to reject.
"""

from __future__ import annotations

import functools
from typing import Dict

import numpy as np

RGB = (0.299, 0.587, 0.114)


def _requant(x, a_qmax, qmax_codes):
    import jax.numpy as jnp
    x = jnp.maximum(x, 0)
    amax = jnp.max(x, axis=tuple(range(1, x.ndim)), keepdims=True)
    scale = jnp.maximum(amax, 1e-8).astype(x.dtype) / a_qmax
    codes = jnp.clip(jnp.round(x / scale), 0, qmax_codes)
    return codes, scale


def _quant_weight(w, w_qmax, levels: int):
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True)
    s = jnp.maximum(amax, 1e-8).astype(w.dtype) / w_qmax
    return jnp.clip(jnp.round(w / s), -levels, levels), s


def _no_fma(x):
    """An exact identity that keeps ``a * b + c`` from being contracted
    into one fused multiply-add, which rounds once instead of twice."""
    import jax.numpy as jnp
    return jnp.nextafter(x, x)


def _acquire(intens, pool: int):
    """RGB -> gray fused with pool x pool mean pooling, taps summed in
    (row, column, channel) order, each tap weighted by rgb[c] / pool**2."""
    import jax.numpy as jnp
    dt = intens.dtype
    acc = None
    for di in range(pool):
        for dj in range(pool):
            for c in range(intens.shape[-1]):
                coef = np.float32(1.0 / (pool * pool)) * np.float32(RGB[c])
                term = intens[:, di::pool, dj::pool, c] * jnp.asarray(coef, dt)
                acc = term if acc is None else acc + term
    return acc[..., None]


def _max_pool(y, size: int):
    import jax.numpy as jnp
    out = None
    for i in range(size):
        for j in range(size):
            t = y[:, i::size, j::size, :]
            out = t if out is None else jnp.maximum(out, t)
    return out


def _weight_bits(cfg: Dict):
    """w_bits per weighted layer: the first at ``scheme.first``, the rest
    at ``scheme.rest`` (Lightator-MX)."""
    first, rest = cfg["scheme"]["first"], cfg["scheme"]["rest"]
    names = [l["name"] for l in cfg["layers"] if l["kind"] in ("conv", "dense")]
    return {n: (first if i == 0 else rest)["w_bits"] for i, n in enumerate(names)}


def forward(cfg: Dict, params, frames, a_qmax, w_qmax: Dict, dtype):
    """Logits [B, n_classes] (float32) for float32 frames [B, H, W, C]."""
    import jax
    import jax.numpy as jnp
    bits = _weight_bits(cfg)
    a_bits = cfg["scheme"]["rest"]["a_bits"]
    qmax_codes = (1 << a_bits) - 1
    a_qmax = a_qmax.astype(dtype)
    x, act_scale = _requant(frames.astype(dtype), a_qmax, qmax_codes)
    for layer in cfg["layers"]:
        kind = layer["kind"]
        if kind == "ca":
            g = _acquire(x * act_scale, layer["pool"])
            x, act_scale = _requant(g, a_qmax, qmax_codes)
        elif kind == "flatten":
            flat = (x * act_scale).reshape(x.shape[0], -1)
            x, act_scale = _requant(flat, a_qmax, qmax_codes)
        elif kind in ("conv", "dense"):
            p = params[layer["name"]]
            levels = (1 << (bits[layer["name"]] - 1)) - 1
            q, s = _quant_weight(p["w"].astype(dtype),
                                 w_qmax[layer["name"]].astype(dtype), levels)
            if kind == "conv":
                k = layer["kernel"]
                if layer["stride"] != 1:
                    raise ValueError("the reference knows stride-1 convs only")
                pad = (k - 1) // 2 if layer["padding"] == "SAME" else 0
                acc = jax.lax.conv_general_dilated(
                    x.astype(jnp.bfloat16), q.astype(jnp.bfloat16),
                    window_strides=(layer["stride"],) * 2,
                    padding=((pad, k - 1 - pad),) * 2,
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    preferred_element_type=jnp.float32)
                scale = act_scale * s.reshape(1, 1, 1, -1)
            else:
                acc = jnp.dot(x.astype(jnp.bfloat16), q.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
                scale = act_scale * s.reshape(1, -1)
            out = _no_fma(acc.astype(dtype) * scale) + p["b"].astype(dtype)
            if layer["act"] == "none":
                x = out
                continue
            y = jnp.maximum(out, 0)
            if layer.get("pool"):
                y = _max_pool(y, layer["pool"][1])
            x, act_scale = _requant(y, a_qmax, qmax_codes)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    return x.astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _jitted(cfg_key: str, dtype_name: str):
    import json
    import jax
    import jax.numpy as jnp
    cfg = json.loads(cfg_key)
    dtype = jnp.dtype(dtype_name)
    return jax.jit(lambda params, frames, a_qmax, w_qmax:
                   forward(cfg, params, frames, a_qmax, w_qmax, dtype))


def logits(cfg: Dict, params, frames: np.ndarray, dtype: str = "float32",
           chunk: int = 16) -> np.ndarray:
    """Run the reference over ``frames`` in chunks of ``chunk`` frames
    (one compiled program; the last chunk is zero-padded), -> numpy."""
    import json
    import jax.numpy as jnp
    fn = _jitted(json.dumps({"layers": cfg["layers"],
                             "scheme": cfg["scheme"]}, sort_keys=True),
                 dtype)
    bits = _weight_bits(cfg)
    a_qmax = jnp.float32((1 << cfg["scheme"]["rest"]["a_bits"]) - 1)
    w_qmax = {n: jnp.float32((1 << (b - 1)) - 1) for n, b in bits.items()}
    out = []
    for off in range(0, len(frames), chunk):
        part = frames[off:off + chunk]
        real = len(part)
        if real < chunk:
            part = np.concatenate(
                [part, np.zeros((chunk - real, *part.shape[1:]), part.dtype)])
        out.append(np.asarray(fn(params, jnp.asarray(part), a_qmax,
                                 w_qmax))[:real])
    return np.concatenate(out)
