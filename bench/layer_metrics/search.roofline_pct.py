"""The tiled search program's share of its roofline, in %.

Least time = bytes / HBM peak, with bytes = work x k x (4 d + 4): every
beam expansion the program counts (``work``, from ``with_stats``) reads k
neighbour ids (4 bytes each) and k float32 rows of d values. Memory bounds
it: an expansion does about 3 d k operations on those 4 d k bytes, far
below the chip's operations per byte. Device time: the program's time in
the trace.
"""


def read(ctx):
    runs, secs = ctx["trace"].module_seconds("search_tiled")
    work = ctx["counters"].get("work")
    if not runs or not work:
        return None
    cfg = ctx["run"].config
    k, d = cfg["search"]["k"], cfg["dim"]
    least = work * k * (4 * d + 4) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
