"""Device microseconds per chunk of the replay program's leaf ops under no
``kway.*`` scope: the scan's own bookkeeping and copies (profiler trace,
``bench/trace_scopes.py``)."""
from bench import trace_scopes


def read(ctx):
    return trace_scopes.phase_us(ctx, "other")
