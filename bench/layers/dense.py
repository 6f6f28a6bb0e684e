"""A fully connected layer: weights ``[fan_in, fan_out]`` and a bias, then
ReLU and the CRC requant, or, with ``act`` ``none``, the dequantized output
itself (the logits of a last layer), with scale 1.

Entry keys: ``name``, ``fan_in``, ``fan_out``, ``act`` (``relu`` | ``none``).
"""

ACTS = ("relu", "none")


def weights(layer):
    return [(layer["name"], (layer["fan_in"], layer["fan_out"]))]


def program(layer):
    from repro.core.accelerator import DenseSpec
    return (DenseSpec(layer["name"], layer["fan_in"], layer["fan_out"],
                      act=layer["act"]),)


def reference(x, scale, layer, params, q):
    import jax.numpy as jnp
    if layer["act"] not in ACTS:
        raise ValueError(f"{layer['name']}: the reference knows the "
                         f"activations {ACTS}, not {layer['act']!r}")
    w, s = q.weight(params, layer["name"])
    acc = jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                  preferred_element_type=jnp.float32)
    out = (q.no_fma(acc.astype(q.dtype) * (scale * s.reshape(1, -1)))
           + params[layer["name"]]["b"].astype(q.dtype))
    if layer["act"] == "none":
        return out, jnp.ones((), q.dtype)
    return q.requant(jnp.maximum(out, 0))


def work(hwc, layer, q):
    macs = layer["fan_in"] * layer["fan_out"]
    c = layer["fan_out"]
    out_bits = 32 if layer["act"] == "none" else q["a_bits"]
    return ([{"name": layer["name"], "macs": macs,
              "weight_bytes": macs * q["w_bits"][layer["name"]] / 8 + c * 4,
              "act_bytes": c * out_bits / 8}], (1, 1, c))
