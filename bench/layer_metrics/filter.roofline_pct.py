"""The filtered search program's share of its roofline, in %.

Least time = bytes / HBM peak, with bytes from :func:`least_bytes`: every
beam expansion the program counts (``work``) reads k neighbour ids, the k
float32 rows of d values and the k uint32 label words of those neighbours.
Memory bounds it, as in the unfiltered search (about 3 d k operations on
4 d k bytes). Device time: the program's time in the trace.
"""


def least_bytes(work: int, k: int, d: int) -> int:
    """Bytes the filtered beam must read: work x k x (4 d + 4 + 4)."""
    return work * k * (4 * d + 4 + 4)


def read(ctx):
    runs, secs = ctx["trace"].module_seconds("search_tiled")
    work = ctx["counters"].get("work")
    if not runs or not work:
        return None
    cfg = ctx["run"].config
    least = least_bytes(work, cfg["search"]["k"], cfg["dim"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
