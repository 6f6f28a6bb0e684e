"""Dense work and least bytes of a configuration, from its layer list.

Independent of any kernel: what the network needs, not what an
implementation spends. Each entry's rows come from the ``work`` face of
its kind file (``bench/layers/<kind>.py``); this module adds the input
frame and sums.

- Ops: a conv is H_out * W_out * C_in * C_out * k**2 MACs, the compressive
  acquisition H_out * W_out * pool**2 * C_in MACs, a dense layer
  fan_in * fan_out MACs; one MAC is 2 ops. Pooling and requant are not
  counted. These are dense counts: a Winograd-style or sparse
  implementation would need them revisited.
- Bytes: the input frame as submitted (float32) once, each weight tensor
  at the scheme's bit width (biases at 32 bits), and each layer's output
  once: activation codes at the scheme's activation width, the logits at
  32 bits.
- Least time of a layer: max(ops / int8 peak, bytes / HBM bandwidth); of a
  batch, the sum over layers, with the weights read once per batch.
"""

from __future__ import annotations

from pathlib import Path
from typing import Dict, List

import cells

ROOT = Path(__file__).resolve().parents[1]


def layers(cfg: Dict, root: Path = ROOT) -> List[Dict]:
    """Per layer: name, MACs per frame, weight bytes (per batch) and
    activation bytes per frame, from each entry's kind's ``work`` face;
    the input frame's bytes go to the first row."""
    hwc = tuple(cfg["input_hwc"])
    in_bytes = hwc[0] * hwc[1] * hwc[2] * 4
    q = {"a_bits": cfg["scheme"]["rest"]["a_bits"],
         "w_bits": cells.weight_bits(root, cfg)}
    out = []
    for layer in cfg["layers"]:
        rows, hwc = cells.layer_kind(root, layer["kind"]).work(hwc, layer, q)
        out.extend(rows)
    out[0]["act_bytes"] = in_bytes + out[0]["act_bytes"]
    return out


def ops_per_frame(cfg: Dict, root: Path = ROOT) -> float:
    return 2.0 * sum(l["macs"] for l in layers(cfg, root))


def least_time_s(cfg: Dict, batch: int, peak: Dict,
                 root: Path = ROOT) -> float:
    """Least device time of one batch of ``batch`` frames on a chip with
    ``peak`` (a row of bench/peaks.json)."""
    ops_s = peak["int8_ops_per_s"]
    bw = peak["hbm_bytes_per_s"]
    return sum(max(2.0 * l["macs"] * batch / ops_s,
                   (l["weight_bytes"] + l["act_bytes"] * batch) / bw)
               for l in layers(cfg, root))
