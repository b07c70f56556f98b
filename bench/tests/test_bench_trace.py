"""The trace reduction, on a hand-made trace and on a trace recorded on a
TPU v5e (``fixtures/trace_phi4_v5e.json``: 16 ms of the generation cell's
traced window, op names cut to their short form)."""
import json

import numpy as np

from bench_helpers import FIXTURES
from benchlib import trace

MS = 1e6


def _hand():
    ops = [["%a = f32[2] fusion(x)", 0 * MS, 2 * MS],
           ["%b = f32[2] fusion(x)", 1 * MS, 2 * MS],     # overlaps a
           ["%c = (f32[2]) custom-call(x)", 5 * MS, 1 * MS],
           ["%a = f32[2] fusion(x)", 9 * MS, 3 * MS]]     # runs past 10 ms
    mods = [["jit_step(1)", 0 * MS, 3 * MS], ["jit_k(2)", 5 * MS, 1 * MS],
            ["jit_step(1)", 9 * MS, 3 * MS]]
    host = [["bench.trace", 0 * MS, 10 * MS],
            ["engine.decode", 0 * MS, 4 * MS],
            ["stage.retrieval", 4 * MS, 5 * MS],
            ["stage.db_search", 4.5 * MS, 1 * MS]]
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": mods}},
            "host": host}


def test_busy_time_is_the_union_of_op_intervals_in_the_window():
    tr = _hand()
    win = trace.window(tr)
    assert win == (0.0, 10 * MS)
    # [0, 3) + [5, 6) + [9, 10) = 5 ms
    assert abs(trace.busy(tr, win) - 5e-3) < 1e-12


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    tr = _hand()
    gaps = dict(trace.idle_gaps(tr, trace.window(tr)))
    # [3, 5): midpoint 4.0 in engine.decode (0-4) and retrieval (4-9):
    # the shorter, engine.decode; [6, 9): midpoint 7.5 in retrieval only
    assert abs(gaps["engine.decode"] - 2e-3) < 1e-12
    assert abs(gaps["stage.retrieval"] - 3e-3) < 1e-12


def test_top_ops_program_under_a_span_and_short_names():
    tr = _hand()
    win = trace.window(tr)
    ops = dict(trace.top_ops(tr, win))
    assert abs(ops["a (fusion)"] - 3e-3) < 1e-12       # 2 ms + 1 ms clipped
    assert abs(ops["c (custom-call)"] - 1e-3) < 1e-12
    assert trace.program_under(tr, win, "engine.decode") == "jit_step(1)"
    assert trace.program_under(tr, win, "stage.db_search") == "jit_k(2)"
    assert trace.program_under(tr, win, "nothing") is None


def _busy_by_sampling(tr, win, step_ns=1000.0):
    ops = next(iter(tr["devices"].values()))["XLA Ops"]
    t = np.arange(win[0], win[1], step_ns) + step_ns / 2
    on = np.zeros(len(t), bool)
    for _, s, d in ops:
        on |= (t >= s) & (t < s + d)
    return on.sum() * step_ns / 1e9


def test_the_recorded_chip_trace_reduces_as_sampling_says():
    tr = json.loads((FIXTURES / "trace_phi4_v5e.json").read_text())
    win = trace.window(tr)
    busy = trace.busy(tr, win)
    assert 0 < busy <= (win[1] - win[0]) / 1e9
    assert abs(busy - _busy_by_sampling(tr, win)) < 2e-5
    idle = sum(v for _, v in trace.idle_gaps(tr, win, top=100))
    assert abs(busy + idle - (win[1] - win[0]) / 1e9) < 1e-9
    decode = trace.program_under(tr, win, "engine.decode")
    assert decode is not None and decode.startswith("jit_")
    assert trace.events_named(tr, win, decode)
