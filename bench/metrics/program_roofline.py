"""The program's share of its roofline over the part of the window the
profiler recorded, in %: the least time of the frames completed in it
(bench/work.py, at the int8 peak and the HBM bandwidth of
bench/peaks.json, weights read once per batch of the traffic's largest
bucket) over the device busy time of every chip used in it."""


def read(ctx):
    tr, c = ctx["trace"], ctx["parts"]["trace"]
    if tr is None or c is None or tr["busy_s"] <= 0 or c["frames"] <= 0:
        return None
    busy = tr["busy_s"] * ctx["chips"]
    return 100.0 * ctx["least_time_per_frame_s"] * c["frames"] / busy
