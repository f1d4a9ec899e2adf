"""Device microseconds per chunk of the replay program's leaf ops under the
``tinylfu.admit`` scope (``CacheBackend.access_admitted``: the victim
peek's probe and victim order, the candidate's and the victim's estimates
and their comparison), booked to the outermost ``tinylfu.*`` scope
(profiler trace, ``bench/trace_tinylfu.py``).  The peek's probe and victim
order are the cache's own ``kway.probe`` and ``kway.victims`` code, so in
the same cell ``kway_us.probe`` and ``kway_us.victims`` hold that share as
well."""
from bench import trace_tinylfu


def read(ctx):
    return trace_tinylfu.scope_us(ctx, "admit")
