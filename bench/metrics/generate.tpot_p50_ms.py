"""Median time per output token of the window's answers (``GenStats
.tpot_s``: each request's own gaps between tokens on the engine's clock),
in ms."""
import numpy as np


def read(ctx):
    t = [x for x in ctx.tpot_s if x > 0]
    return float(np.median(t) * 1e3) if t else None
