"""Find a cell's pieces by name, from data files under the checkout.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it; adding a cell or a metric adds files and
entries and edits none:

- ``BENCHMARK.json`` ``configs[].file``: the configuration (sizes, scheme,
  layer list);
- ``bench/traffic/<traffic>.json``: the traffic mix and the server knobs
  it is served with;
- ``bench/metrics/<metric>.py``: a reader ``read(ctx) -> float | None``
  for one per-layer metric;
- ``bench/layers/<kind>.py``: one kind of entry of a configuration's
  ``layers`` list, the ``kind`` the entry names;
- ``bench/peaks.json``: the chip's peaks, keyed by JAX's ``device_kind``.

A layer kind file defines four faces, and nothing else in the harness knows
a kind: ``bench/model.py``, ``bench/reference.py`` and ``bench/work.py``
only walk the layer list and call them.

- ``weights(layer) -> [(name, shape), ...]``: the weight tensors the entry
  holds, in order; each has a bias of ``shape[-1]``. The scheme's
  ``first`` width goes to the configuration's first tensor, ``rest`` to
  every later one, and ``bench/model.py`` draws each one He-normal.
- ``program(layer) -> tuple``: the entry as the program's layer IR objects
  (``repro.core.accelerator``), imported inside the function only.
- ``reference(x, scale, layer, params, q) -> (x, scale)``: the plain
  forward of the entry, activation codes and their per-frame scale in and
  out; ``q`` is ``reference.Quant`` (the precision, the divisors and the
  shared CRC, weight and pooling steps). A block keeps its own skip.
- ``work(hwc, layer, q) -> (rows, hwc)``: the dense work of the entry at
  input shape ``hwc``, as rows ``{name, macs, weight_bytes, act_bytes}``
  (``bench/work.py``), and its output shape; ``q`` holds ``a_bits`` and
  each tensor's ``w_bits``.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Tuple


def load_benchmark(root: Path) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(root: Path, bench: Dict, name: str) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(root / c["file"]) as f:
                return json.load(f)
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(root: Path, name: str) -> Dict:
    path = root / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no traffic file {path}")
    with open(path) as f:
        return json.load(f)


def peaks(root: Path, device_kind: str) -> Dict:
    with open(root / "bench" / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def metric_reader(root: Path, name: str) -> Callable[[Dict], object]:
    """``read`` of ``bench/metrics/<name>.py`` (the name may hold dots)."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reader {path} for per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def layer_kind(root: Path, kind: str) -> ModuleType:
    """The module ``bench/layers/<kind>.py``, with the four faces above."""
    path = root / "bench" / "layers" / f"{kind}.py"
    if not path.is_file():
        raise KeyError(f"no file bench/layers/{kind}.py ({path}) for layer "
                       f"kind {kind!r}")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_" + kind.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tensors(root: Path, cfg: Dict) -> List[Tuple[str, tuple]]:
    """Every weight tensor of the configuration, ``(name, shape)`` in
    configuration order."""
    return [t for layer in cfg["layers"]
            for t in layer_kind(root, layer["kind"]).weights(layer)]


def weight_bits(root: Path, cfg: Dict) -> Dict[str, int]:
    """w_bits per weight tensor: the first at ``scheme.first``, the rest
    at ``scheme.rest`` (Lightator-MX)."""
    first, rest = cfg["scheme"]["first"], cfg["scheme"]["rest"]
    return {name: (first if i == 0 else rest)["w_bits"]
            for i, (name, _) in enumerate(tensors(root, cfg))}


def per_layer_metrics(bench: Dict, workload: str):
    """The per-layer metrics this cell reports, in file order."""
    return [m for m in bench["per_layer"]
            if "workloads" not in m or workload in m["workloads"]]


def end_to_end_metrics(bench: Dict, workload: str):
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]]
