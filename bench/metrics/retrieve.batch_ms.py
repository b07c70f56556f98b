"""Retrieve stage per batch: the StageTimer's ``retrieval`` time (index
copy to the device included) averaged over the window's batches, in ms."""


def read(ctx):
    s = ctx.stage_series.get("retrieval", [])
    return float(sum(s) / len(s) * 1e3) if s else None
