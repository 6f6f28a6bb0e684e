#!/usr/bin/env python3
"""The control of ``correct``: the reference at the precision below the
configuration's, put in the program's place.

    python3 bench/control.py --workload <name> --seconds 3 --seeds 1 2 3

For each seed it drives a whole run of the cell through the harness
(``run.run_cell``: the same weights, frames, traffic, server and check)
with the server's device execution replaced, batch by batch, by the
reference in bfloat16 (the configuration states float32), and prints the
run's ``correct`` and the numbers it compared beside their limits. The
control has to come out not correct; its smallest ``logit_err`` is the
upper reading that the limit is set below. Runs on the chip (it fails
without a TPU, as a run does); the benchmark's own runs do not run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Dict

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

import model  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402


def hooks(cfg: Dict, seed: int):
    """Server hooks that answer each batch with the bfloat16 reference on
    the run's own weights (made again from the seed), padded to the
    batch's bucket as the program's executable pads it."""
    from repro.serve.server import Hooks
    params = model.make_params(cfg, seed)

    def execute(name, device, frames, bucket, default):
        return reference.logits(cfg, params, np.asarray(frames), "bfloat16",
                                chunk=bucket)

    return Hooks(execute=execute)


def control_run(c: Dict, seed: int, seconds: float) -> Dict:
    """One run of the prepared cell ``c`` with the control in the
    program's place; returns its ``correct`` and checks."""
    r = run.run_cell(c["bench"], c["cell"], c["cfg"], c["traffic"], seed,
                     seconds, False, c["options"],
                     c["devices"][:c["cell"]["chips"]], c["peak"],
                     hooks=hooks(c["cfg"], seed))
    return {"seed": seed, "correct": r["correct"], "checks": r["checks"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    c = run.prepare(args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload,
                          **control_run(c, seed, args.seconds)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
