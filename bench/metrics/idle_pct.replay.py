"""Idle share of the chip over the traced replay window (least busy chip):
one minus the union of its operation intervals over the window."""
from bench import trace_reduce


def read(ctx):
    return trace_reduce.idle_pct(ctx.trace) if ctx.trace else None
