"""Device milliseconds per ``add_reverse_edges`` pass, from the trace."""
from bench.trace_metrics import module_ms_per_run


def read(ctx):
    return module_ms_per_run(ctx, "add_reverse_edges")
