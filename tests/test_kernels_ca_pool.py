"""ca_pool kernel vs oracle: shape/pool/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compressive import ca_coefficients
from repro.kernels.ca_pool.ops import ca_pool
from repro.kernels.ca_pool.ref import ca_pool_ref, ca_pool_ref_generic


@pytest.mark.parametrize("shape,pool", [
    ((2, 32, 32, 3), 2), ((1, 28, 28, 3), 4), ((3, 16, 24, 3), 2),
    ((1, 64, 64, 3), 8), ((4, 8, 8, 3), 2),
])
def test_matches_compressive_acquire(shape, pool):
    img = jax.random.uniform(jax.random.PRNGKey(shape[1]), shape)
    got = ca_pool(img, pool)
    want = ca_pool_ref(img, pool)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_custom_coefficients():
    img = jax.random.uniform(jax.random.PRNGKey(0), (2, 12, 12, 3))
    coeffs = jax.random.uniform(jax.random.PRNGKey(1), (3, 3, 3))
    got = ca_pool(img, 3, coeffs=coeffs)
    want = ca_pool_ref_generic(img, coeffs, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtypes(dtype):
    img = jax.random.uniform(jax.random.PRNGKey(2), (1, 16, 16, 3)).astype(dtype)
    got = ca_pool(img, 2)
    assert got.dtype == dtype
    want = ca_pool_ref(img.astype(jnp.float32), 2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=2e-2, atol=1e-2)


def test_single_channel_pool():
    img = jax.random.uniform(jax.random.PRNGKey(3), (2, 8, 8, 1))
    got = ca_pool(img, 2, rgb_to_gray=True,
                  coeffs=ca_coefficients(2, 1))
    want = img.reshape(2, 4, 2, 4, 2, 1).mean(axis=(2, 4))[..., 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("shape,pool", [((2, 16, 16, 3), 2), ((1, 12, 20, 2), 4)])
def test_per_channel_matches_compressive_acquire(shape, pool):
    """Pooling each channel on its own (no gray mix) runs the kernel too."""
    from repro.core.compressive import compressive_acquire
    img = jax.random.uniform(jax.random.PRNGKey(4), shape)
    got = ca_pool(img, pool, per_channel=True)
    want = compressive_acquire(img, pool, rgb_to_gray=False)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ["pallas", "reference"])
def test_ca_acquire_counts_its_backend(backend):
    """Every CA stage is counted under the backend it was traced for; the
    pallas backend never falls back to the reference, per-channel included."""
    from repro import obs
    from repro.kernels import dispatch

    def count(name):
        c = obs.REGISTRY.get(name)
        return c.get() if c is not None else 0

    img = jax.random.uniform(jax.random.PRNGKey(5), (1, 8, 8, 3))
    before = {n: count(f"dispatch.ca.{n}") for n in ("pallas", "reference")}
    with dispatch.use_backend(backend):
        dispatch.ca_acquire(img, 2, rgb_to_gray=True)
        dispatch.ca_acquire(img, 2, rgb_to_gray=False)
    after = {n: count(f"dispatch.ca.{n}") for n in ("pallas", "reference")}
    other = "reference" if backend == "pallas" else "pallas"
    assert after[backend] - before[backend] == 2
    assert after[other] == before[other]
