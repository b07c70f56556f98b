"""Time the retrieve layer spends handing host arrays to the device per
search: the program's ``stage.retrieval.h2d`` spans
(``JaxVectorDB._search_arrays``) in the traced window, summed, over the
benchmark's ``stage.db_search`` spans there, in ms.  A transfer that runs
on after its call returns shows under ``stage.retrieval.wait`` instead.
Read only from a run traced on an accelerator (a device plane in the
trace): on the CPU no bytes cross to a device."""
from benchlib import trace


def read(ctx):
    if ctx.trace is None or ctx.trace_win is None or not ctx.trace["devices"]:
        return None
    tot = {n: (c, s) for n, c, s in trace.span_totals(ctx.trace,
                                                       ctx.trace_win)}
    if "stage.retrieval.h2d" not in tot or "stage.db_search" not in tot:
        return None
    return tot["stage.retrieval.h2d"][1] / tot["stage.db_search"][0] * 1e3
