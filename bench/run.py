#!/usr/bin/env python3
"""One cell of the benchmark, on the chip, from the root of a checkout:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

Set-up builds the cell's pipeline through the program's registry, makes the
corpus and the generator's weights from ``--seed`` on the device, and runs
every batch size the cell's batcher can form once (so nothing compiles in
the window; compiled programs come from ``<checkout>/.jax_cache``).  The
window then offers the cell's open-loop load for ``--seconds`` and waits for
the answers.  ``--trace 0`` prints the cell's end-to-end metrics;
``--trace 1`` traces part of the window with the profiler and prints the
per-layer metrics instead.  Either way the answers are then compared with
the plain references (``benchlib/checks.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``busy_s`` and
``window_s`` too when traced), ``breakdown`` when traced, and last
``checks``, each number compared beside its limit; the same numbers are the
last lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, it prints no result and exits 1.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# The platform the benchmark measures; a test steers it to "cpu" to rehearse
# a run at a tiny size.
PLATFORM = "tpu"


def _paths(root: Path) -> bool:
    if not (root / "src" / "repro").is_dir():
        return False
    for p in (str(BENCH), str(root / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    return True


def _percentile(xs, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q))


def end_to_end(w, recall):
    """The cell's end-to-end metrics from the window's records."""
    deadline_lat = []
    answered = 0
    close = w.t0 + w.seconds
    for r in w.records:
        if r["ok"]:
            deadline_lat.append(r["end"] - r["due"])
            answered += r["end"] <= close
        else:
            # a query never answered counts as missing: at least the wait
            # until the run stopped waiting for it
            deadline_lat.append(w.end - r["due"])
    out = {"answer_p90_ms": _percentile(deadline_lat, 90) * 1e3,
           "answered_qps": answered / w.seconds}
    if recall is not None:
        out["recall_at_10"] = recall
    return out


def stalls(w, log) -> str:
    """Where the window's longest waits were, for a run whose tail reads
    far off: the slowest searches, the longest pause between two searches,
    the latest submission, and the slowest batch of each stage."""
    s = sorted(log.searches, key=lambda x: x[0])
    slow = sorted(s, key=lambda x: x[0] - x[1])[:3]
    pause = max(((b[0] - a[1], a[1]) for a, b in zip(s, s[1:])),
                default=(0.0, w.t0))
    late = max((r["submit"] - r["due"] for r in w.records), default=0.0)
    worst = {k: max(v) * 1e3 for k, v in w.stage_series.items() if v}
    return ("slowest searches (s into window, ms, queries): "
            + ", ".join(f"{a - w.t0:.3f} {(b - a) * 1e3:.1f} {n}"
                        for a, b, n, _ in slow)
            + f"; longest pause between searches {pause[0] * 1e3:.1f} ms "
            f"at {pause[1] - w.t0:.3f} s; latest submission {late * 1e3:.3f}"
            f" ms; slowest batch by stage (ms): "
            + ", ".join(f"{k} {v:.1f}" for k, v in sorted(worst.items())))


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default="",
                    help="also write the reduced trace as JSON here")
    ap.add_argument("--control", action="store_true",
                    help="also read the controls (never in benchmark runs)")
    args = ap.parse_args(argv)
    if not _paths(root):
        print(f"bench: FAIL: no src/repro under {root}", file=sys.stderr)
        return 2
    from benchlib import spec as spec_lib
    from benchlib.device import DeviceError, gate, peaks_for
    try:
        cell = spec_lib.load_cell(args.workload, root, root / "bench")
        devs = gate(cell.chips, PLATFORM)
        peaks = peaks_for(devs[0].device_kind)
    except (spec_lib.SpecError, DeviceError) as e:
        print(f"bench: FAIL: {e}", file=sys.stderr)
        return 1
    res = run_cell(cell, devs, peaks, args)
    checks = res.pop("checks")
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    res["checks"] = checks
    print(json.dumps(res))
    return 0


def compile_cache() -> None:
    """JAX's persistent cache in ``<checkout>/.jax_cache`` (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def run_cell(cell, devs, peaks, args) -> dict:
    from benchlib import cell as cell_lib
    from benchlib import checks as checks_lib
    from benchlib import device, trace as trace_lib

    compile_cache()
    cfg, traffic = cell.config, cell.traffic
    log = cell_lib.Log()
    served = cell_lib.set_up(cfg, traffic, args.seed, log)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    w = cell_lib.run_window(cfg, traffic, served, args.seed, args.seconds,
                            log, trace_dir)
    w.end = time.perf_counter()
    setup_s = w.t0 - T_START
    peak = device.peak_bytes(devs)

    # what the references need of the program's run, then its state freed
    db = served.pipe.db
    if cfg["corpus"]["kind"] == "synthetic_text":
        texts = [db.chunks[s].text for s in range(db.n_slots)]
        row_of = {s: s for s in range(db.n_slots)}
    else:
        texts = None
        row_of = {}
        for _, ids, _ in log.retrievals:
            for row in ids:
                for i in row.tolist():
                    if i >= 0 and i not in row_of:
                        row_of[i] = db.get_chunk(i).doc_id
    del served, db
    gc.collect()

    ctx = SimpleNamespace(
        records=w.records,
        batch_sizes=w.batch_sizes, stage_series=w.stage_series,
        tpot_s=w.tpot_s, log=log, peaks=peaks, peak_bytes=peak, cfg=cfg,
        model=cell.costs.from_config(cfg["model"]) if cell.costs else None,
        trace=None, trace_win=None, trace_pc=w.trace_pc)
    breakdown = dev_extra = None
    if trace_dir is not None:
        tr = trace_lib.load(trace_lib.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        win = trace_lib.window(tr)
        ctx.trace, ctx.trace_win = tr, win
        if args.trace_out:
            Path(args.trace_out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.trace_out).write_text(json.dumps(
                trace_lib.trim(tr, 0.016, 1.0) if win else tr))
        if win is not None:
            for row in trace_lib.top_modules(tr, win):
                print(f"program {row[0]}: {row[1]:.6f} s in {row[2]} "
                      f"launches", file=sys.stderr)
            for row in trace_lib.span_totals(tr, win):
                print(f"host span {row[0]}: {row[1]} spans, {row[2]:.6f} s",
                      file=sys.stderr)
            dev_extra = {"busy_s": trace_lib.busy(tr, win),
                         "window_s": (win[1] - win[0]) / 1e9}
            breakdown = {"device_ops": trace_lib.top_ops(tr, win),
                         "idle_gaps": trace_lib.idle_gaps(tr, win)}

    t_check = time.perf_counter()
    numbers = {"serve.unanswered": float(sum(not r["ok"]
                                             for r in w.records))}
    ret = checks_lib.retrieval(cfg, args.seed, log.retrievals, texts, row_of,
                               control=args.control)
    numbers.update(ret["numbers"])
    controls = dict(ret.get("control", {}))
    if cell.reference is not None:
        gen = checks_lib.generation(cfg, args.seed, log.retired,
                                    cell.reference, control=args.control)
        numbers.update(gen["numbers"])
        controls.update(gen.get("control", {}))
    v = checks_lib.verdict(numbers, cfg["limits"])
    t_check = time.perf_counter() - t_check

    metrics = {}
    if args.trace:
        from benchlib.spec import metric_reader
        for m in cell.per_layer:
            val = metric_reader(m.name, cell.bench_dir)(ctx)
            if val is not None:
                metrics[m.name] = {"value": val, "unit": m.unit}
    else:
        e2e = end_to_end(w, ret["recall_at_10"])
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m.name in e2e:
                metrics[m.name] = {"value": e2e[m.name], "unit": m.unit}
    dev = device.describe(devs)
    dev["memory_peak_bytes"] = peak
    if dev_extra:
        dev.update(dev_extra)
    late = [r["submit"] - r["due"] for r in w.records]
    if log.retired:
        import numpy as np
        q = np.percentile([len(t) for _, t, _ in log.retired],
                          [0, 10, 50, 90, 100])
        print(f"prompt tokens min/p10/p50/p90/max: "
              f"{' / '.join(str(int(x)) for x in q)} over "
              f"{len(log.retired)} answers", file=sys.stderr)
    print(f"window: {len(w.records)} queries due in {w.seconds} s, "
          f"{sum(r['ok'] for r in w.records)} answered; set-up "
          f"{setup_s:.2f} s; references {t_check:.2f} s; whole run "
          f"{time.perf_counter() - T_START:.2f} s; compiles in window "
          f"{w.compiles}; full collections in window {w.gc_full[0]}, "
          f"longest {w.gc_full[1]:.3f} s; client late "
          f"p50 {statistics.median(late) * 1e3 if late else 0:.3f} ms",
          file=sys.stderr)
    print(stalls(w, log), file=sys.stderr)
    out = {"correct": v["ok"], "attempted": len(w.records),
           "failed": int(numbers["serve.unanswered"]), "metrics": metrics,
           "device": dev, "compiles_in_window": w.compiles}
    if args.trace and breakdown is not None:
        out["breakdown"] = breakdown
    if args.control:
        out["controls"] = controls
    out["checks"] = v["checks"]
    return out


if __name__ == "__main__":
    sys.exit(main())
