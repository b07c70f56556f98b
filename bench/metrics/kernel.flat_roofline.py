"""The flat top-k kernel's share of its roofline: the least bytes of each
launch (the corpus once, queries and results) over that kernel's device
time in the trace (its Pallas custom call, ``*topk*``), over the peak HBM
bandwidth, in %."""
from benchlib import costs, trace



def _is_kernel(name):
    short = trace.short_name(name)
    return "topk" in short and short.endswith("(custom-call)")


def read(ctx):
    if ctx.trace is None or ctx.trace_win is None or ctx.trace_pc is None:
        return None
    ev = [e for e in trace.events_named(ctx.trace, ctx.trace_win, "",
                                        trace.OPS, match=_is_kernel)]
    a, b = ctx.trace_pc
    launches = [s for s in ctx.log.searches if a <= 0.5 * (s[0] + s[1]) < b]
    if not ev or not launches:
        return None
    c = ctx.cfg["corpus"]
    per = sum(costs.flat_launch_bytes(c["rows"], c["dim"], nq, k)
              for _, _, nq, k in launches) / len(launches)
    t = sum(d for _, d in ev)
    return 100.0 * per * len(ev) / t / ctx.peaks.hbm_bytes_s
