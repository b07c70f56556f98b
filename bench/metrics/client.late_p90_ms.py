"""How late the load generator submitted: the 90th percentile of
submit - due over the window's queries (host clock), in ms."""
import numpy as np


def read(ctx):
    late = [r["submit"] - r["due"] for r in ctx.records]
    return float(np.percentile(late, 90) * 1e3) if late else None
