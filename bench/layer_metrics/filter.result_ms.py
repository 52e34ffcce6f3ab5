"""Device milliseconds per call of the tiled search program under the
``beam.filter`` scope (label gather, predicate, result-list merge), from
the trace; None where the program has no such scope."""
from bench.trace_scopes import scope_ms


def read(ctx):
    return scope_ms("search_tiled", "beam.filter")
