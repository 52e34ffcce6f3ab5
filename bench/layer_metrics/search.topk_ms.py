"""Device milliseconds per call of the tiled search program under the
``beam.topk`` scope (the beam's top-k merge), from the trace."""
from bench.trace_scopes import scope_ms


def read(ctx):
    return scope_ms("search_tiled", "beam.topk")
