"""Arithmetic shared by the per-layer metric readers (``layer_metrics/``):
each reader is one metric's name bound to one of these."""
from __future__ import annotations


def module_ms_per_run(ctx: dict, pattern: str) -> float | None:
    """Device milliseconds per run of the XLA modules whose name contains
    ``pattern``, from the trace; None when the trace holds none."""
    runs, secs = ctx["trace"].module_seconds(pattern)
    return 1e3 * secs / runs if runs else None


def idle_pct(ctx: dict) -> float | None:
    """Share of the traced window in which no operation ran on the device."""
    t = ctx["trace"]
    if t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def counter(ctx: dict, name: str, scale: float = 1.0) -> float | None:
    v = ctx["counters"].get(name)
    return None if v is None else scale * v
