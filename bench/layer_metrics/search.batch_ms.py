"""Device milliseconds of the tiled search program per call (one call
searches the whole query set), from the trace."""
from bench.trace_metrics import module_ms_per_run


def read(ctx):
    return module_ms_per_run(ctx, "search_tiled")
