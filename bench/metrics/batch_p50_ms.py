"""Median batch latency of the traced window's served client path (host
clock); steadier than the p99 it sits beside."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.latencies_s, 50)) * 1e3 if ctx.latencies_s else None
