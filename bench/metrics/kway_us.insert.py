"""Device microseconds per chunk of the replay program's leaf ops under the
``kway.insert`` scope, its lane scatters booked by their indices where the
compiler drops their names (profiler trace, ``bench/trace_scopes.py``)."""
from bench import trace_scopes


def read(ctx):
    return trace_scopes.phase_us(ctx, "insert")
