"""Device microseconds per chunk of the replay program's leaf ops under the
``tinylfu.record`` scope (``admission.record``: doorkeeper, count-min
counters and the aging ``cond``), booked to the outermost ``tinylfu.*``
scope (profiler trace, ``bench/trace_tinylfu.py``).  In the same cell,
``kway_us.probe`` and ``kway_us.victims`` also hold the share of the
victim peek that runs under ``tinylfu.admit``; ``tinylfu_us.admit`` holds
it too."""
from bench import trace_tinylfu


def read(ctx):
    return trace_tinylfu.scope_us(ctx, "record")
