"""Compressive acquisition: RGB to gray fused with ``pool x pool`` mean
pooling on the sensor, then the CRC requant. It holds no weights.

Entry keys: ``pool`` (it has to divide the frame), ``rgb_to_gray`` (the
reference knows only the fused RGB-to-gray form, on three channels).
"""


def weights(layer):
    return []


def program(layer):
    from repro.core.accelerator import CASpec
    return (CASpec(pool=layer["pool"], rgb_to_gray=layer["rgb_to_gray"]),)


def reference(x, scale, layer, params, q):
    if not layer["rgb_to_gray"] or x.shape[-1] != 3:
        raise ValueError("the ca reference knows RGB-to-gray on three "
                         f"channels only, not {layer} on {x.shape[-1]}")
    return q.requant(q.acquire(x * scale, layer["pool"]))


def work(hwc, layer, q):
    h, w, c = hwc
    p = layer["pool"]
    h, w = h // p, w // p
    macs = h * w * p * p * c
    c = 1 if layer["rgb_to_gray"] else c
    return ([{"name": "ca", "macs": macs, "weight_bytes": 0,
              "act_bytes": h * w * c * q["a_bits"] / 8}], (h, w, c))
