"""Device-idle milliseconds per search call inside the program's
``streaming/search`` span (entry point and dispatch), from the trace."""
from bench.trace_scopes import span_idle_ms


def read(ctx):
    return span_idle_ms("streaming/search")
