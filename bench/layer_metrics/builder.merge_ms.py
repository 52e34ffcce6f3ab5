"""Device milliseconds per ``update_neighbors`` sweep under the
``rnnd.merge`` scope (row sorts and the candidate-edge merge), from the
trace."""
from bench.trace_scopes import scope_ms


def read(ctx):
    return scope_ms("update_neighbors", "rnnd.merge")
