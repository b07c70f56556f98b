"""The readers of the program's own spans and counters
(``retrieve.h2d_mb``, ``retrieve.h2d_ms``, ``generate.ttft_p90_ms`` and
``generate.decode_idle_ms``), which a CPU run never reads: each reads its
number from a trace with a device plane, as a run on the chip has, and
nothing from the same trace without one, as a run on the CPU has."""
import pytest

from bench_helpers import BENCH
from benchlib import spec, trace
from repro import obs


class _Ctx:
    def __init__(self, tr, records=()):
        self.trace = tr
        self.trace_win = trace.window(tr)
        self.records = list(records)


def _chip(host, ops=()):
    return {"devices": {"/device:TPU:0": {trace.OPS: list(ops)}},
            "host": [["bench.trace", 0.0, 100.0]] + host}


def _h2d_mb():
    t0 = obs.REGISTRY.clock.now()
    obs.count("db.h2d_bytes", 6e6)
    obs.count("db.search_calls", 4)
    t1 = obs.REGISTRY.clock.now()
    obs.count("db.h2d_bytes", 5e6)          # after the last answer
    obs.count("db.search_calls")
    return _Ctx(_chip([]), [{"due": t0, "end": t1}]), 1.5


def _h2d_ms():
    host = [["stage.db_search", 10.0, 40.0],
            ["stage.retrieval.h2d", 12.0, 3.0],
            ["stage.db_search", 60.0, 30.0],
            ["stage.retrieval.h2d", 62.0, 5.0]]
    # 8 ns of copies over two searches
    return _Ctx(_chip(host)), 4e-6


def _ttft_p90_ms():
    t0 = obs.REGISTRY.clock.now()
    for v in (0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0, 1.1):
        obs.observe("serve.ttft_s", v)
    t1 = obs.REGISTRY.clock.now()
    obs.observe("serve.ttft_s", 50.0)      # after the last answer
    return _Ctx(_chip([]), [{"due": t0, "end": t1}]), 1000.0


def _decode_idle_ms():
    host, ops = [], []
    for i in range(4):                     # four 20 ns decode steps
        t = 10.0 + 20.0 * i
        host += [["engine.decode", t, 20.0],
                 ["engine.decode.upload", t, 2.0],
                 ["engine.decode.launch", t + 2.0, 1.0],
                 ["engine.decode.sync", t + 3.0, 15.0],
                 ["engine.decode.bookkeep", t + 18.0, 2.0]]
        ops.append(["op", t + 5.0, 11.0])
    # the 9 ns gaps before steps 1-3 fall in an upload span; those before
    # step 0 and after step 3 lie outside the engine's spans
    return _Ctx(_chip(host, ops)), 27e-6 / 4


CASES = {"retrieve.h2d_mb": _h2d_mb, "retrieve.h2d_ms": _h2d_ms,
         "generate.ttft_p90_ms": _ttft_p90_ms,
         "generate.decode_idle_ms": _decode_idle_ms}


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_chip_trace_is_read(name):
    ctx, want = CASES[name]()
    assert spec.metric_reader(name, BENCH)(ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_trace_without_a_device_plane_reads_nothing(name):
    ctx, _ = CASES[name]()
    ctx.trace["devices"] = {}
    assert spec.metric_reader(name, BENCH)(ctx) is None
