"""Beam expansions per query of the filtered search (``with_stats``
``work`` / queries searched in the window)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("queries") or not c.get("work"):
        return None
    return c["work"] / c["queries"]
