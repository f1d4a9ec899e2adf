"""Device microseconds per chunk of the replay program's leaf ops under the
``kway.hit`` scope, its ``meta_a`` scatter-max booked by its indices where
the compiler drops its name (profiler trace, ``bench/trace_scopes.py``)."""
from bench import trace_scopes


def read(ctx):
    return trace_scopes.phase_us(ctx, "hit")
