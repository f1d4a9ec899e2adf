"""Share of the HBM roofline reached by the replay program of the cache
behind TinyLFU admission: the algorithm's bytes for the window's requests
(``bytes_tinylfu``: the flat cache's, plus the sketch's record and the
victim's estimate) at the chip's peak bandwidth, over the device time of
the replay's scan program (its ``XLA Modules`` events in the trace)."""
from bench import bytes_tinylfu, peaks, trace_reduce

PROGRAM = r"^jit_fn\("


def read(ctx):
    if not ctx.trace:
        return None
    t = trace_reduce.device_time(ctx.trace, PROGRAM, modules=True)
    if t <= 0:
        return None
    need = bytes_tinylfu.request_bytes(ctx.cell.config) * ctx.attempted
    return 100.0 * need / peaks.peak(ctx.device_kind)["hbm_bytes_per_s"] / t
