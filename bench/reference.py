"""Plain reference of a configuration's forward pass, as served.

Written from the configuration file alone, in straightforward ``jax.numpy``;
it imports nothing of the program under test. The semantics are the
Lightator device's with per-frame calibration, the hardware's
frame-per-pass mode that the server runs. This module holds the steps that
every layer kind shares, and walks the layer list; each entry's own forward
is the ``reference`` face of its kind file, ``bench/layers/<kind>.py``
(``bench/cells.py`` says what a kind file holds), given the activation
codes, their per-frame scale and a ``Quant``:

- CRC requant (``Quant.requant``): ``x = max(x, 0)``;
  ``scale = max(max_frame(x), 1e-8) / 15``;
  ``codes = clip(round(x / scale), 0, 15)``, the max taken over each frame.
  The frames enter through it.
- Weights (``Quant.weight``), quantized per output channel:
  ``s = max(|w|, 1e-8) / w_qmax``, ``q = clip(round(w / s), -levels,
  levels)``. A kind accumulates codes by levels as an exact integer sum
  (bfloat16 carries both exactly, the product accumulates in float32, and
  every sum stays under 2**24), then dequantizes ``acc * (act_scale * s)``,
  adds the bias behind ``Quant.no_fma``, and applies its activation, its
  pool (``Quant.max_pool``) and the requant, in that order.
- Compressive acquisition (``Quant.acquire``): intensities ``codes *
  scale`` summed over the ``pool x pool`` window and the three channels
  with weights ``(0.299, 0.587, 0.114) / pool**2``, taps in (row, column,
  channel) order.

The output is the last entry's codes times its scale: the logits after a
last dense layer with no activation (which returns its dequantized output
with scale 1), an image after a spatial one.

The divisors 15 and ``w_qmax`` enter as traced arguments, so the compiler
cannot turn ``x / 15`` into ``x * (1 / 15)``, which rounds differently.

``dtype`` selects the precision of every float step. float32 is the
configuration's; bfloat16 is the control, the nearest precision below,
which the comparison has to reject.
"""

from __future__ import annotations

import dataclasses
import functools
from pathlib import Path
from typing import Dict

import numpy as np

import cells

ROOT = Path(__file__).resolve().parents[1]

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


@dataclasses.dataclass(frozen=True)
class Quant:
    """What a layer kind's ``reference`` face is given beside its entry:
    the precision of every float step, the activation divisor and code
    range, each weight tensor's divisor and levels, and the steps every
    kind shares. The divisors are traced arguments in ``dtype``."""

    dtype: object
    a_qmax: object
    qmax_codes: int
    w_qmax: Dict[str, object]
    levels: Dict[str, int]

    no_fma = staticmethod(_no_fma)
    max_pool = staticmethod(_max_pool)
    acquire = staticmethod(_acquire)

    def requant(self, y):
        """CRC requant of ``y``: codes and their per-frame scale."""
        return _requant(y, self.a_qmax, self.qmax_codes)

    def weight(self, params, name: str):
        """Tensor ``name``'s weight codes and per-output-channel scale."""
        return _quant_weight(params[name]["w"].astype(self.dtype),
                             self.w_qmax[name].astype(self.dtype),
                             self.levels[name])


def forward(cfg: Dict, params, frames, a_qmax, w_qmax: Dict, dtype,
            root: Path = ROOT):
    """The dequantized output (float32) for float32 frames [B, H, W, C]:
    logits [B, n_classes] after a last dense layer, an image after a
    spatial one. Each entry runs its kind's ``reference`` face."""
    import jax.numpy as jnp
    a_bits = cfg["scheme"]["rest"]["a_bits"]
    q = Quant(dtype=dtype, a_qmax=a_qmax.astype(dtype),
              qmax_codes=(1 << a_bits) - 1, w_qmax=w_qmax,
              levels={n: (1 << (b - 1)) - 1
                      for n, b in cells.weight_bits(root, cfg).items()})
    x, act_scale = q.requant(frames.astype(dtype))
    for layer in cfg["layers"]:
        x, act_scale = cells.layer_kind(root, layer["kind"]).reference(
            x, act_scale, layer, params, q)
    return (x * act_scale).astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _jitted(cfg_key: str, dtype_name: str, root: str):
    import json
    import jax
    import jax.numpy as jnp
    cfg = json.loads(cfg_key)
    dtype = jnp.dtype(dtype_name)
    return jax.jit(lambda params, frames, a_qmax, w_qmax:
                   forward(cfg, params, frames, a_qmax, w_qmax, dtype,
                           Path(root)))


def logits(cfg: Dict, params, frames: np.ndarray, dtype: str = "float32",
           chunk: int = 16, root: Path = ROOT) -> np.ndarray:
    """Run the reference over ``frames`` in chunks of ``chunk`` frames
    (one compiled program; the last chunk is zero-padded), -> numpy."""
    import json
    import jax.numpy as jnp
    fn = _jitted(json.dumps({"layers": cfg["layers"],
                             "scheme": cfg["scheme"]}, sort_keys=True),
                 dtype, str(root))
    a_qmax = jnp.float32((1 << cfg["scheme"]["rest"]["a_bits"]) - 1)
    w_qmax = {n: jnp.float32((1 << (b - 1)) - 1)
              for n, b in cells.weight_bits(root, cfg).items()}
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
