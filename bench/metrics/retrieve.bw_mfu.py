"""The retrieve layer's share of the chip's peak HBM bandwidth over the
traced window: the least bytes of every search in it (the corpus once,
queries and results) over window x peak bandwidth, in %."""
from benchlib import costs


def read(ctx):
    if ctx.trace_pc is None:
        return None
    a, b = ctx.trace_pc
    c = ctx.cfg["corpus"]
    by = sum(costs.flat_launch_bytes(c["rows"], c["dim"], nq, k)
             for t0, t1, nq, k in ctx.log.searches if a <= 0.5 * (t0 + t1) < b)
    return 100.0 * by / ((b - a) * ctx.peaks.hbm_bytes_s) if by else None
