"""Share of the HBM roofline reached by the flat cache's replay program:
the algorithm's bytes for the window's requests (``bytes_model``) at the
chip's peak bandwidth, over the device time of the replay's scan program
(its ``XLA Modules`` events in the trace)."""
from bench import bytes_model, peaks, trace_reduce

PROGRAM = r"^jit_fn\("


def read(ctx):
    if not ctx.trace:
        return None
    t = trace_reduce.device_time(ctx.trace, PROGRAM, modules=True)
    if t <= 0:
        return None
    need = bytes_model.request_bytes(ctx.cell.config) * ctx.attempted
    return 100.0 * need / peaks.peak(ctx.device_kind)["hbm_bytes_per_s"] / t
