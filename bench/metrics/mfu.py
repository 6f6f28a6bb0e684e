"""The whole step's share of the chips' peak, in %: frames completed in
the untraced host part of the window over its seconds, times dense ops
per frame (bench/work.py), over the int8 peak of every chip used. The
profiler slows serving, so the rate is not taken while it records."""


def read(ctx):
    c = ctx["parts"]["host"]
    if c is None or c["seconds"] <= 0 or c["frames"] <= 0:
        return None
    peak = ctx["peak"]["int8_ops_per_s"] * ctx["chips"]
    return 100.0 * c["frames"] / c["seconds"] * ctx["ops_per_frame"] / peak
