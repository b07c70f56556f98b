"""The tail of a cell offered more than it serves: the 90th percentile of
due -> answer returned over the window's answered queries (host clock), in
ms.  Above the knee the queue grows all through the window, so this swings
with the smallest change in throughput and is read, not judged; a query
never answered makes the run not correct instead."""
import numpy as np


def read(ctx):
    lat = [r["end"] - r["due"] for r in ctx.records if r["ok"]]
    return float(np.percentile(lat, 90) * 1e3) if lat else None
