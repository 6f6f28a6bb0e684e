"""A configuration file -> the program under test, its weights and frames.

The configuration (``bench/configs/<name>.json``) describes the network as
plain data: input shape, layer list, [W:A] scheme and weight init. This
module turns it into the program's own ``repro.Program`` (each entry's
layer IR from the ``program`` face of its kind file, ``bench/layers``) with
the weight tensors that the kinds' ``weights`` faces declare, made on the
device in one jitted call from the run's seed, and into a host pool of
input frames drawn from the same seed. The plain reference
(``bench/reference.py``) reads the same file and the same weights; nothing
here is shared with it but data.
"""

from __future__ import annotations

import math
from pathlib import Path
from typing import Dict

import numpy as np

import cells

MASK32 = 0xFFFFFFFF
ROOT = Path(__file__).resolve().parents[1]


def seed_key(seed: int):
    """A JAX PRNG key that depends on all bits of ``seed``.

    ``PRNGKey`` keeps only the low 32 bits of a larger Python int, so two
    seeds that differ above bit 31 would give the same weights."""
    import jax
    key = jax.random.PRNGKey(seed & MASK32)
    return jax.random.fold_in(key, (seed >> 32) & MASK32)


def param_count(cfg: Dict, root: Path = ROOT) -> int:
    return sum(math.prod(shape) + shape[-1]
               for _, shape in cells.tensors(root, cfg))


def make_params(cfg: Dict, seed: int, root: Path = ROOT):
    """Weights and biases of every declared tensor on the default device,
    in one jitted call.

    He-normal weights (std sqrt(2 / fan_in)) keep a ReLU stack's
    activations in range at any depth; biases are normal with
    ``init.bias_std`` so that the bias add of every epilogue does work.
    """
    import jax
    import jax.numpy as jnp
    tensors = cells.tensors(root, cfg)
    bias_std = float(cfg["init"]["bias_std"])

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(tensors))
        out = {}
        for (name, shape), k in zip(tensors, keys):
            fan_in = math.prod(shape[:-1])
            kw, kb = jax.random.split(k)
            out[name] = {
                "w": jax.random.normal(kw, shape, jnp.float32)
                * np.float32(math.sqrt(2.0 / fan_in)),
                "b": jax.random.normal(kb, (shape[-1],), jnp.float32)
                * np.float32(bias_std)}
        return out

    params = init(seed_key(seed))
    jax.block_until_ready(params)
    return params


def make_frames(cfg: Dict, n: int, seed: int) -> np.ndarray:
    """``n`` distinct frames [n, H, W, C], float32 intensities in [0, 1)."""
    rng = np.random.default_rng([seed, 1])
    return rng.random((n, *cfg["input_hwc"]), dtype=np.float32)


def scheme(cfg: Dict):
    """The configuration's [W:A] scheme as the program's scheme object."""
    from repro.core.quant import MixedPrecisionScheme, WASpec
    s = cfg["scheme"]
    first = WASpec(s["first"]["w_bits"], s["first"]["a_bits"])
    rest = WASpec(s["rest"]["w_bits"], s["rest"]["a_bits"])
    return MixedPrecisionScheme(first, rest)


def program_layers(cfg: Dict, root: Path = ROOT) -> tuple:
    """The configuration's layer list as the program's layer IR."""
    return tuple(spec for layer in cfg["layers"]
                 for spec in cells.layer_kind(root, layer["kind"])
                 .program(layer))


def make_program(cfg: Dict, params, root: Path = ROOT):
    from repro.core.program import Program
    return Program(program_layers(cfg, root), params,
                   tuple(cfg["input_hwc"]), name=cfg["name"])
