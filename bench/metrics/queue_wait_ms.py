"""Mean time a request waited in the server's queue before its batch
closed, from the ``serve.request.queue_wait`` spans of the part of the
window over which the program's spans are collected."""


def read(ctx):
    waits = [(s["t1_ns"] - s["t0_ns"]) / 1e6 for s in ctx["spans"]
             if s["name"] == "serve.request.queue_wait"]
    return sum(waits) / len(waits) if waits else None
