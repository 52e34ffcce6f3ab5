"""Device milliseconds per call of the tiled search program under the
``beam.visited`` scope (in-beam dedup and the visited table), from the
trace."""
from bench.trace_scopes import scope_ms


def read(ctx):
    return scope_ms("search_tiled", "beam.visited")
