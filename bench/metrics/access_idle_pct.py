"""Share of the chip's idle time in the traced window that falls inside a
``cache.access`` span, by interval intersection (profiler trace,
``bench/trace_scopes.py``)."""
from bench import trace_scopes


def read(ctx):
    return trace_scopes.access_idle_pct(ctx)
