"""Median duration of the traced window's ``cache.access`` spans: the
program's host time per served batch, from the call into the backend's
``access`` to its return (profiler trace, ``bench/trace_scopes.py``)."""
from bench import trace_scopes


def read(ctx):
    return trace_scopes.access_host_us(ctx)
