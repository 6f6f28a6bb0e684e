"""Public wrapper for the Compressive Acquisitor kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.compressive import ca_coefficients
from repro.kernels.ca_pool import kernel as K
from repro.kernels.dispatch import default_interpret


def ca_pool(img: jnp.ndarray, pool: int = 2,
            rgb_to_gray: bool | None = None,
            coeffs: jnp.ndarray | None = None,
            per_channel: bool = False) -> jnp.ndarray:
    """Fused RGB->gray + pool x pool mean pooling. img [B,H,W,C] -> [B,H',W'].

    ``coeffs`` overrides the pre-set CA weights (the paper's "configurable"
    compression: any strided weighted acquisition). ``per_channel`` pools
    every channel on its own (no gray mix): -> [B, H', W', C], the
    ``compressive_acquire(rgb_to_gray=False)`` semantics.
    """
    c = img.shape[-1]
    if coeffs is None:
        if rgb_to_gray is None:
            rgb_to_gray = (c == 3)
        if per_channel:
            coeffs = jnp.ones((pool, pool, c), jnp.float32) / (pool * pool)
        elif rgb_to_gray:
            coeffs = ca_coefficients(pool, c)
        else:
            coeffs = jnp.ones((pool, pool, c), jnp.float32) / (pool * pool * c)
    return K.ca_pool_kernel(img, coeffs.astype(jnp.float32), pool=pool,
                            per_channel=per_channel,
                            interpret=default_interpret())
