"""Host stages per batch: the StageTimer's ``query_embed`` and ``rerank``
time over the window, divided by the batches run, in ms."""


def read(ctx):
    s = ctx.stage_series
    n = len(s.get("retrieval", []))
    if not n:
        return None
    return float((sum(s.get("query_embed", [])) + sum(s.get("rerank", [])))
                 / n * 1e3)
