"""Device milliseconds per ``update_neighbors`` sweep under the
``rnnd.prune`` scope (the chunked RNG prune), from the trace."""
from bench.trace_scopes import scope_ms


def read(ctx):
    return scope_ms("update_neighbors", "rnnd.prune")
