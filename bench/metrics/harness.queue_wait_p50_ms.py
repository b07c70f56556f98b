"""Median wait from when a query was due to when the serving harness
started its batch (``RequestRecord.start_s`` - due), in ms."""
import numpy as np


def read(ctx):
    w = [r["start"] - r["due"] for r in ctx.records if r["ok"]]
    return float(np.median(w) * 1e3) if w else None
