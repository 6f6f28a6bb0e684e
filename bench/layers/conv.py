"""A 2-D convolution: weights ``[k, k, c_in, c_out]`` and a bias, at
``stride`` with ``SAME`` or ``VALID`` padding, then ReLU (or nothing), an
optional max pool, and the CRC requant.

Entry keys: ``name``, ``c_in``, ``c_out``, ``kernel``, ``stride``,
``padding``, ``act`` (``relu`` | ``none``), ``pool`` (null or
``["max", size]``; the size has to divide the conv's output).

The geometry is XLA's, as the program states it: ``SAME`` gives
ceil(H / stride) and pads ``total // 2`` before and the rest after, where
``total = max((out - 1) * stride + k - H, 0)``; ``VALID`` gives
``(H - k) // stride + 1`` and no padding. The work is counted at the
output's H x W.
"""

ACTS = ("relu", "none")


def _out(n, layer):
    k, stride = layer["kernel"], layer["stride"]
    if layer["padding"] == "VALID":
        return (n - k) // stride + 1
    if layer["padding"] == "SAME":
        return -(-n // stride)
    raise ValueError(f"{layer['name']}: unknown padding "
                     f"{layer['padding']!r}")


def _pads(n, layer):
    if layer["padding"] == "VALID":
        return (0, 0)
    total = max((_out(n, layer) - 1) * layer["stride"] + layer["kernel"] - n,
                0)
    return (total // 2, total - total // 2)


def _pool_size(layer):
    if not layer.get("pool"):
        return None
    kind, size = layer["pool"]
    if kind != "max":
        raise ValueError(f"{layer['name']}: the conv kind knows max pools "
                         f"only, not {layer['pool']}")
    return size


def weights(layer):
    k = layer["kernel"]
    return [(layer["name"], (k, k, layer["c_in"], layer["c_out"]))]


def program(layer):
    from repro.core.accelerator import ConvSpec
    pool = tuple(layer["pool"]) if layer.get("pool") else None
    return (ConvSpec(layer["name"], layer["c_in"], layer["c_out"],
                     kernel=layer["kernel"], stride=layer["stride"],
                     padding=layer["padding"], act=layer["act"], pool=pool),)


def reference(x, scale, layer, params, q):
    import jax
    import jax.numpy as jnp
    if layer["act"] not in ACTS:
        raise ValueError(f"{layer['name']}: the reference knows the "
                         f"activations {ACTS}, not {layer['act']!r}")
    size = _pool_size(layer)
    w, s = q.weight(params, layer["name"])
    acc = jax.lax.conv_general_dilated(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        window_strides=(layer["stride"],) * 2,
        padding=tuple(_pads(n, layer) for n in x.shape[1:3]),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    out = (q.no_fma(acc.astype(q.dtype) * (scale * s.reshape(1, 1, 1, -1)))
           + params[layer["name"]]["b"].astype(q.dtype))
    y = jnp.maximum(out, 0) if layer["act"] == "relu" else out
    if size:
        y = q.max_pool(y, size)
    return q.requant(y)


def work(hwc, layer, q):
    h, w, _ = hwc
    k = layer["kernel"]
    h, w = _out(h, layer), _out(w, layer)
    macs = h * w * layer["c_in"] * layer["c_out"] * k * k
    n_w = k * k * layer["c_in"] * layer["c_out"]
    c = layer["c_out"]
    size = _pool_size(layer)
    if size:
        h, w = h // size, w // size
    return ([{"name": layer["name"], "macs": macs,
              "weight_bytes": n_w * q["w_bits"][layer["name"]] / 8 + c * 4,
              "act_bytes": h * w * c * q["a_bits"] / 8}], (h, w, c))
