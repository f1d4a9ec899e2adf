"""Device microseconds per chunk of the replay program's leaf ops under the
``kway.probe`` scope (profiler trace, ``bench/trace_scopes.py``)."""
from bench import trace_scopes


def read(ctx):
    return trace_scopes.phase_us(ctx, "probe")
