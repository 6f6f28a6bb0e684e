"""Frames executed over batch slots executed in the untraced host part of
the window, in %: the share of device batch slots that carried a
request's frame rather than padding (the server's per-program counters)."""


def read(ctx):
    c = ctx["parts"]["host"]
    return 100.0 * c["frames"] / c["slots"] if c and c["slots"] else None
