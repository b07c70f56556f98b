"""Device idle time per decode step under the engine's own decode spans
(``engine.decode.upload``, ``.launch``, ``.sync``, ``.bookkeep``;
``repro.serving.genengine``): the idle gaps of the traced window whose
midpoint falls in one of those spans (the rule of ``trace.idle_gaps``;
nothing nests inside them), summed, over the ``engine.decode.launch``
spans in the window, in ms."""
import bisect

from benchlib import trace

PREFIX = "engine.decode."


def read(ctx):
    tr, win = ctx.trace, ctx.trace_win
    if tr is None or win is None:
        return None
    lines = [p.get(trace.OPS) or p.get(trace.MODULES)
             for p in tr["devices"].values()]
    lines = [ln for ln in lines if ln]
    steps = sum(1 for n, s, _ in tr["host"]
                if n == PREFIX + "launch" and win[0] <= s < win[1])
    if not lines or not steps:
        return None
    spans = sorted((s, s + d) for n, s, d in tr["host"]
                   if n.startswith(PREFIX))
    starts = [a for a, _ in spans]
    busy = trace.union([(max(s, win[0]), min(s + d, win[1]))
                        for _, s, d in lines[0]
                        if min(s + d, win[1]) > max(s, win[0])])
    idle, t = 0.0, win[0]
    for a, b in busy + [(win[1], win[1])]:
        if a > t:
            mid = 0.5 * (t + a)
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and mid <= spans[i][1]:
                idle += a - t
        t = max(t, b)
    return idle / 1e6 / steps
