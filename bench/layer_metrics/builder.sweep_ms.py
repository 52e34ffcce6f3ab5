"""Device milliseconds per ``update_neighbors`` sweep, from the trace."""
from bench.trace_metrics import module_ms_per_run


def read(ctx):
    return module_ms_per_run(ctx, "update_neighbors")
