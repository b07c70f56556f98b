"""Share of the traced window in which no operation ran on the device:
1 - the union of the device's op intervals over the window, in %."""
from benchlib import trace


def read(ctx):
    if ctx.trace is None or ctx.trace_win is None:
        return None
    win = ctx.trace_win
    length = (win[1] - win[0]) / 1e9
    return 100.0 * (1.0 - trace.busy(ctx.trace, win) / length)
