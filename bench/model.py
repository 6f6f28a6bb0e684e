"""A configuration file -> the program under test, its weights and frames.

The configuration (``bench/configs/<name>.json``) describes the network as
plain data: input shape, layer list, [W:A] scheme and weight init. This
module turns it into the program's own ``repro.Program`` (layer IR from
``repro.core.accelerator``) with weights made on the device in one jitted
call from the run's seed, and into a host pool of input frames drawn from
the same seed. The plain reference (``bench/reference.py``) reads the same
file and the same weights; nothing here is shared with it but data.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

MASK32 = 0xFFFFFFFF


def seed_key(seed: int):
    """A JAX PRNG key that depends on all bits of ``seed``.

    ``PRNGKey`` keeps only the low 32 bits of a larger Python int, so two
    seeds that differ above bit 31 would give the same weights."""
    import jax
    key = jax.random.PRNGKey(seed & MASK32)
    return jax.random.fold_in(key, (seed >> 32) & MASK32)


def weighted_layers(cfg: Dict) -> List[Dict]:
    """The conv and dense layers, in order: the ones that hold weights."""
    return [l for l in cfg["layers"] if l["kind"] in ("conv", "dense")]


def weight_shape(layer: Dict):
    if layer["kind"] == "conv":
        k = layer["kernel"]
        return (k, k, layer["c_in"], layer["c_out"])
    return (layer["fan_in"], layer["fan_out"])


def param_count(cfg: Dict) -> int:
    return sum(math.prod(weight_shape(l)) + weight_shape(l)[-1]
               for l in weighted_layers(cfg))


def make_params(cfg: Dict, seed: int):
    """Weights and biases on the default device, in one jitted call.

    He-normal weights (std sqrt(2 / fan_in)) keep a ReLU stack's
    activations in range at any depth; biases are normal with
    ``init.bias_std`` so that the bias add of every epilogue does work.
    """
    import jax
    import jax.numpy as jnp
    layers = weighted_layers(cfg)
    bias_std = float(cfg["init"]["bias_std"])

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(layers))
        out = {}
        for layer, k in zip(layers, keys):
            shape = weight_shape(layer)
            fan_in = math.prod(shape[:-1])
            kw, kb = jax.random.split(k)
            out[layer["name"]] = {
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


def program_layers(cfg: Dict) -> tuple:
    """The configuration's layer list as the program's layer IR."""
    from repro.core.accelerator import CASpec, ConvSpec, DenseSpec, FlattenSpec
    out = []
    for l in cfg["layers"]:
        kind = l["kind"]
        if kind == "ca":
            out.append(CASpec(pool=l["pool"], rgb_to_gray=l["rgb_to_gray"]))
        elif kind == "conv":
            pool = tuple(l["pool"]) if l.get("pool") else None
            out.append(ConvSpec(l["name"], l["c_in"], l["c_out"],
                                kernel=l["kernel"], stride=l["stride"],
                                padding=l["padding"], act=l["act"],
                                pool=pool))
        elif kind == "flatten":
            out.append(FlattenSpec())
        elif kind == "dense":
            out.append(DenseSpec(l["name"], l["fan_in"], l["fan_out"],
                                 act=l["act"]))
        else:
            raise ValueError(f"unknown layer kind {kind!r} in {cfg['name']}")
    return tuple(out)


def make_program(cfg: Dict, params):
    from repro.core.program import Program
    return Program(program_layers(cfg), params, tuple(cfg["input_hwc"]),
                   name=cfg["name"])
