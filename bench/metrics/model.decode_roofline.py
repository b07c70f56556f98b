"""The decode program's share of its roofline: the least bytes of a
decode step (every weight once, the keys and values the active sequences
read, the position each writes) over the decode program's device time in
the trace, over the peak HBM bandwidth, in %.  The decode program has no
stable name: it is the program that takes most device time under the
engine's decode steps."""
from benchlib import trace


def read(ctx):
    if ctx.trace is None or ctx.trace_win is None or ctx.trace_pc is None:
        return None
    program = trace.program_under(ctx.trace, ctx.trace_win, "engine.decode")
    if program is None:
        return None
    ev = trace.events_named(ctx.trace, ctx.trace_win, program)
    a, b = ctx.trace_pc
    steps = [s for s in ctx.log.engine_steps
             if s[0] == "decode" and a <= 0.5 * (s[1] + s[2]) < b]
    if not ev or not steps:
        return None
    per = sum(ctx.model.decode_step_bytes(s[3]) for s in steps) / len(steps)
    t = sum(d for _, d in ev)
    return 100.0 * per * len(ev) / t / ctx.peaks.hbm_bytes_s
