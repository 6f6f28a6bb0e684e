"""Flatten: each frame's intensities as one vector, then the CRC requant.
It holds no weights and does no counted work. Entry keys: none.
"""


def weights(layer):
    return []


def program(layer):
    from repro.core.accelerator import FlattenSpec
    return (FlattenSpec(),)


def reference(x, scale, layer, params, q):
    return q.requant((x * scale).reshape(x.shape[0], -1))


def work(hwc, layer, q):
    h, w, c = hwc
    return [], (1, 1, h * w * c)
