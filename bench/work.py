"""Dense work and least bytes of a configuration, from its layer list.

Independent of any kernel: what the network needs, not what an
implementation spends.

- Ops: a conv is H_out * W_out * C_in * C_out * k**2 MACs, the compressive
  acquisition H_out * W_out * pool**2 * C_in MACs, a dense layer
  fan_in * fan_out MACs; one MAC is 2 ops. Pooling and requant are not
  counted. These are dense counts: a Winograd-style or sparse
  implementation would need them revisited.
- Bytes: the input frame as submitted (float32) once, each weighted
  layer's weights at the scheme's bit width (biases at 32 bits), and each
  layer's output once: activation codes at the scheme's activation width,
  the logits at 32 bits.
- Least time of a layer: max(ops / int8 peak, bytes / HBM bandwidth); of a
  batch, the sum over layers, with the weights read once per batch.
"""

from __future__ import annotations

from typing import Dict, List


def _weight_bits(cfg: Dict) -> List[int]:
    s = cfg["scheme"]
    n = sum(1 for l in cfg["layers"] if l["kind"] in ("conv", "dense"))
    return [s["first"]["w_bits"]] + [s["rest"]["w_bits"]] * (n - 1)


def layers(cfg: Dict) -> List[Dict]:
    """Per layer: name, MACs per frame, weight bytes (per batch) and
    activation bytes per frame."""
    h, w, c = cfg["input_hwc"]
    a_bits = cfg["scheme"]["rest"]["a_bits"]
    wbits = iter(_weight_bits(cfg))
    out = []
    first = True
    for l in cfg["layers"]:
        kind = l["kind"]
        in_bytes = h * w * c * 4 if first else 0
        if kind == "flatten":
            h, w, c = 1, 1, h * w * c
            continue
        if kind == "ca":
            p = l["pool"]
            h, w = h // p, w // p
            macs = h * w * p * p * c
            c = 1 if l["rgb_to_gray"] else c
            out.append({"name": "ca", "macs": macs, "weight_bytes": 0,
                        "act_bytes": in_bytes + h * w * c * a_bits / 8})
        elif kind == "conv":
            k = l["kernel"]
            macs = h * w * l["c_in"] * l["c_out"] * k * k
            n_w = k * k * l["c_in"] * l["c_out"]
            c = l["c_out"]
            if l.get("pool"):
                h, w = h // l["pool"][1], w // l["pool"][1]
            out.append({"name": l["name"], "macs": macs,
                        "weight_bytes": n_w * next(wbits) / 8 + c * 4,
                        "act_bytes": in_bytes + h * w * c * a_bits / 8})
        elif kind == "dense":
            macs = l["fan_in"] * l["fan_out"]
            c = l["fan_out"]
            out_bits = 32 if l["act"] == "none" else a_bits
            out.append({"name": l["name"], "macs": macs,
                        "weight_bytes": macs * next(wbits) / 8 + c * 4,
                        "act_bytes": in_bytes + c * out_bits / 8})
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        first = False
    return out


def ops_per_frame(cfg: Dict) -> float:
    return 2.0 * sum(l["macs"] for l in layers(cfg))


def least_time_s(cfg: Dict, batch: int, peak: Dict) -> float:
    """Least device time of one batch of ``batch`` frames on a chip with
    ``peak`` (a row of bench/peaks.json)."""
    ops_s = peak["int8_ops_per_s"]
    bw = peak["hbm_bytes_per_s"]
    return sum(max(2.0 * l["macs"] * batch / ops_s,
                   (l["weight_bytes"] + l["act_bytes"] * batch) / bw)
               for l in layers(cfg))
