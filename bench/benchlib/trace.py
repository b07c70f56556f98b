"""From a profiler trace to device busy time, kernel and program times,
and idle gaps labelled with what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a small
plain form that the reduction works on, and that a test fixture can hold:

    {"devices": {plane: {line: [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

Only the device planes' ``XLA Ops`` and ``XLA Modules`` lines are kept, and
only the host spans the benchmark itself opens (``jax.profiler
.TraceAnnotation`` names with one of ``HOST_PREFIXES``), which share the
device trace's clock.  ``bench.trace`` marks the traced window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

OPS, MODULES = "XLA Ops", "XLA Modules"
HOST_PREFIXES = ("bench.", "stage.", "engine.", "harness.")
WINDOW_SPAN = "bench.trace"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")

Interval = Tuple[float, float]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(xplane_path: str) -> Dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    devices: Dict[str, Dict[str, List]] = {}
    host: List = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS, MODULES):
                    lines[line.name] = [[e.name, float(e.start_ns),
                                         float(e.duration_ns)]
                                        for e in line.events]
            if lines:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"devices": devices, "host": host}


def window(tr: Dict) -> Optional[Interval]:
    """The traced window: the ``bench.trace`` host span."""
    spans = [(s, s + d) for n, s, d in tr["host"] if n == WINDOW_SPAN]
    return max(spans, key=lambda iv: iv[1] - iv[0]) if spans else None


def _clip(events: List, win: Interval) -> List[Interval]:
    out = []
    for _, s, d in events:
        a, b = max(s, win[0]), min(s + d, win[1])
        if b > a:
            out.append((a, b))
    return out


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _device_line(lines: Dict) -> List:
    return lines.get(OPS) or lines.get(MODULES) or []


def busy(tr: Dict, win: Interval) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    planes = [p for p in tr["devices"].values() if _device_line(p)]
    if not planes:
        return 0.0
    tot = 0.0
    for lines in planes:
        tot += sum(b - a for a, b in union(_clip(_device_line(lines), win)))
    return tot / len(planes) / 1e9


def label_at(tr: Dict, t: float) -> str:
    """The innermost benchmark host span open at ``t``."""
    best = None
    for n, s, d in tr["host"]:
        if n != WINDOW_SPAN and s <= t <= s + d:
            if best is None or d < best[1]:
                best = (n, d)
    return best[0] if best else "outside the benchmark's spans"


def idle_gaps(tr: Dict, win: Interval, top: int = 10) -> List[List]:
    """Idle seconds of the first device plane, summed by the host span each
    gap's midpoint fell in, largest first."""
    planes = [p for p in tr["devices"].values() if _device_line(p)]
    if not planes:
        return []
    busy_iv = union(_clip(_device_line(planes[0]), win))
    gaps, t = [], win[0]
    for a, b in busy_iv:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if win[1] > t:
        gaps.append((t, win[1]))
    by: Dict[str, float] = {}
    for a, b in gaps:
        lab = label_at(tr, 0.5 * (a + b))
        by[lab] = by.get(lab, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:top]]


_OPCODE = re.compile(r"[\]})]\s+([a-z][a-z0-9\-]*)\(")


def short_name(hlo: str) -> str:
    """``%fusion.92 (fusion)`` for an ``XLA Ops`` event's HLO text."""
    head, _, rest = hlo.partition(" = ")
    m = _OPCODE.search(rest)
    return f"{head.lstrip('%')} ({m.group(1)})" if m else head.lstrip("%")


def top_ops(tr: Dict, win: Interval, top: int = 10) -> List[List]:
    """Device seconds by operation on the first device plane.  An op that
    holds others (a ``while`` over layers) counts their time too."""
    planes = [p for p in tr["devices"].values() if _device_line(p)]
    if not planes:
        return []
    by: Dict[str, float] = {}
    for n, s, d in _device_line(planes[0]):
        iv = _clip([[n, s, d]], win)
        if iv:
            k = short_name(n)
            by[k] = by.get(k, 0.0) + (iv[0][1] - iv[0][0]) / 1e9
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:top]]


def events_named(tr: Dict, win: Interval, needle: str,
                 line: str = MODULES, match=None) -> List[Tuple[float, float]]:
    """(start_ns, seconds) of the first device plane's events on ``line``
    whose name contains ``needle`` (or passes ``match``) and that start
    inside the window."""
    planes = list(tr["devices"].values())
    if not planes:
        return []
    ok = match or (lambda n: needle in n)
    return [(s, d / 1e9) for n, s, d in planes[0].get(line, [])
            if ok(n) and win[0] <= s < win[1]]


def program_under(tr: Dict, win: Interval, span: str) -> Optional[str]:
    """The program that takes most device time under the host spans called
    ``span`` (a device event counts where its midpoint falls): how the
    benchmark finds a program that has no stable name of its own."""
    planes = list(tr["devices"].values())
    spans = sorted((s, s + d) for n, s, d in tr["host"] if n == span)
    if not planes or not spans:
        return None
    starts = [a for a, _ in spans]
    by: Dict[str, float] = {}
    for n, s, d in planes[0].get(MODULES, []):
        mid = s + 0.5 * d
        if not win[0] <= s < win[1]:
            continue
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid <= spans[i][1]:
            by[n] = by.get(n, 0.0) + d
    return max(by, key=by.get) if by else None


def span_totals(tr: Dict, win: Interval) -> List[List]:
    """Host spans in the window: [name, count, seconds], most time first."""
    by: Dict[str, List] = {}
    for n, s, d in tr["host"]:
        if n != WINDOW_SPAN and win[0] <= s < win[1]:
            e = by.setdefault(n, [0, 0.0])
            e[0] += 1
            e[1] += d / 1e9
    return [[k, v[0], v[1]] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1][1])]


def top_modules(tr: Dict, win: Interval, top: int = 10) -> List[List]:
    """Device seconds and launches by program name on the first plane."""
    planes = list(tr["devices"].values())
    if not planes:
        return []
    by: Dict[str, List] = {}
    for n, s, d in planes[0].get(MODULES, []):
        if win[0] <= s < win[1]:
            e = by.setdefault(n, [0.0, 0])
            e[0] += d / 1e9
            e[1] += 1
    return [[k, v[0], v[1]] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1][0])[:top]]


def trim(tr: Dict, seconds: float, start: float = 0.0) -> Dict:
    """``seconds`` of the traced window from ``start`` seconds into it, with
    op names cut to ``short_name``: small enough for a test fixture."""
    win = window(tr)
    a = win[0] + start * 1e9
    end = a + seconds * 1e9
    keep = (lambda s: a <= s < end)
    out = {"devices": {}, "host": [[n, s, min(d, end - s)]
                                   for n, s, d in tr["host"]
                                   if keep(s) and n != WINDOW_SPAN]}
    out["host"].append([WINDOW_SPAN, a, seconds * 1e9])
    for plane, lines in tr["devices"].items():
        out["devices"][plane] = {
            ln: [[short_name(n) if ln == OPS else n, s, min(d, end - s)]
                 for n, s, d in evs if keep(s)]
            for ln, evs in lines.items()}
    return out
