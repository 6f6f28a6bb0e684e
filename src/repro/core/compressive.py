"""Compressive Acquisitor (CA) — paper Sec. 3.2.

The CA fuses RGB->grayscale conversion and kxk average pooling into a single
weighted-sum MAC executed in ONE optical cycle, by pre-setting the MR weights
to the product coefficients (paper eq. (1)):

    P_AvgGray = sum_{i in pool} sum_{j in {R,G,B}} (1/k^2) * c_j * P_ij
    c = (0.299, 0.587, 0.114)

Two realizations:
  * ``compressive_acquire`` — the pure-jnp reference (ref for the ca_pool
    Pallas kernel).
  * ``sequence_ca`` — generalization used for LM-family frontends: strided
    mean-pooling of frame/patch embeddings with a fused channel mix. This is
    the "compressive acquisition as a first-class feature" hook for the
    assigned [audio]/[vlm] architectures.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

RGB_COEFFS = (0.299, 0.587, 0.114)


def ca_coefficients(pool: int, channels: int = 3) -> jnp.ndarray:
    """The pre-set MR weights for one CA stride: shape [pool, pool, channels].

    channels==3 -> RGB->gray fused with mean pooling; channels==1 -> pure
    mean pooling (the paper's 'pooling layers implemented within CA banks').
    """
    if channels == 3:
        chan = jnp.asarray(RGB_COEFFS, jnp.float32)
    else:
        chan = jnp.full((channels,), 1.0 / channels, jnp.float32)
    w = jnp.ones((pool, pool, channels), jnp.float32) / float(pool * pool)
    return w * chan[None, None, :]


def window_pool(y: jnp.ndarray, kind: str, size: int) -> jnp.ndarray:
    """``size`` x ``size`` max/avg pooling of [..., H, W, C] with a fixed
    summation order: window taps added row-major, ``((y00 + y01) + y10) +
    y11`` for size 2, then divided by ``size**2``. A reduce over the window
    axes leaves the order to the compiler, which differs between backends
    and between XLA and a Pallas kernel, and f32 addition is not
    associative; the fused kernel adds its taps in this same order."""
    out = None
    for i in range(size):
        for j in range(size):
            t = y[..., i::size, j::size, :]
            out = t if out is None else (
                jnp.maximum(out, t) if kind == "max" else out + t)
    return out if kind == "max" else out / (size * size)


def compressive_acquire(img: jnp.ndarray, pool: int = 2,
                        rgb_to_gray: bool | None = None) -> jnp.ndarray:
    """Fused RGB->gray + pool x pool average pooling (single weighted MAC).

    img: [..., H, W, C] with H, W divisible by pool.
    Returns [..., H/pool, W/pool] (gray) or [..., H/pool, W/pool, C]
    (per-channel pooling when rgb_to_gray=False).
    """
    *lead, h, w, c = img.shape
    if h % pool or w % pool:
        raise ValueError(f"H({h}), W({w}) must be divisible by pool={pool}")
    if rgb_to_gray is None:
        rgb_to_gray = (c == 3)
    if rgb_to_gray:
        x = img.reshape(*lead, h // pool, pool, w // pool, pool, c)
        coeffs = ca_coefficients(pool, c)            # [pool, pool, c]
        # HIGHEST: a TPU einsum otherwise rounds f32 operands to bf16
        return jnp.einsum("...hpwqc,pqc->...hw", x, coeffs,
                          precision=jax.lax.Precision.HIGHEST)
    return window_pool(img, "avg", pool)


def strided_conv_acquire(img: jnp.ndarray, weights: jnp.ndarray,
                         stride: int) -> jnp.ndarray:
    """The CA's other mode: configurable strided convolution at acquisition.

    img: [..., H, W, C]; weights: [k, k, C]; returns [..., H', W'].
    Implemented as patch extraction + the same weighted-sum MAC (one optical
    cycle per strides_per_cycle outputs).
    """
    k = weights.shape[0]
    *lead, h, w, c = img.shape
    h_out = (h - k) // stride + 1
    w_out = (w - k) // stride + 1
    # gather patches [..., h_out, w_out, k, k, c]
    rows = jnp.arange(h_out) * stride
    cols = jnp.arange(w_out) * stride
    patches = img[..., rows[:, None] + jnp.arange(k)[None, :], :, :]
    patches = patches[..., :, :, cols[:, None] + jnp.arange(k)[None, :], :]
    # patches: [..., h_out, k, w_out, k, c] -> weighted sum
    return jnp.einsum("...hpwqc,pqc->...hw", patches, weights)


def upsample_reconstruct(img: jnp.ndarray, factor: int = 2,
                         method: str = "bilinear") -> jnp.ndarray:
    """The CA's inverse: reconstruct a full-resolution frame from a
    compressively acquired one (paper's versatile-processing direction:
    acquisition *and* reconstruction on the same preset-MAC fabric).

    img: [B, H, W, C] -> [B, H*factor, W*factor, C]. ``bilinear`` models
    preset interpolation banks (each output a fixed weighted sum of <= 4
    inputs); ``nearest`` is a pure copy. Deterministic and differentiable —
    the learned deconv head trains through it.
    """
    import jax
    if method not in ("bilinear", "nearest"):
        raise ValueError(f"unknown upsample method {method!r}")
    b, h, w, c = img.shape
    return jax.image.resize(img, (b, h * factor, w * factor, c), method)


def sequence_ca(embeds: jnp.ndarray, factor: int,
                channel_mix: jnp.ndarray | None = None) -> jnp.ndarray:
    """Compressive acquisition for token/frame/patch embedding streams.

    embeds: [..., T, D]; returns [..., T/factor, D]. Mean-pools ``factor``
    consecutive embeddings (the CA's mean pooling) with an optional fused
    per-feature mix (the RGB->gray analogue). Used by the audio/VLM frontends.
    """
    *lead, t, d = embeds.shape
    if t % factor:
        raise ValueError(f"T({t}) must be divisible by factor={factor}")
    x = embeds.reshape(*lead, t // factor, factor, d).mean(axis=-2)
    if channel_mix is not None:
        x = x * channel_mix
    return x
