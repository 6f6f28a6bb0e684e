"""Mean time a device's worker spent staging a batch, in ms: from taking
the batch to the jitted call's return (the staging copy, the
host-to-device put and the launch), from the ``serve.batch.stage`` spans
of the part of the window over which the program's spans are collected.
None where the program records no such span."""


def read(ctx):
    stages = [(s["t1_ns"] - s["t0_ns"]) / 1e6 for s in ctx["spans"]
              if s["name"] == "serve.batch.stage"]
    return sum(stages) / len(stages) if stages else None
