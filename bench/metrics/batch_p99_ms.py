"""99th percentile of the window's batch latencies, host keys in to hits,
values and evictions back on the host (host clock)."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.latencies_s, 99)) * 1e3 if ctx.latencies_s else None
