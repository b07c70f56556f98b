#!/usr/bin/env python3
"""Find the highest rate a cell's served path sustains, once, on the chip:

    python3 bench/sweep.py --workload <name> --rates 2,3,4,5 --seconds 15

One process, one set-up: the cell's pipeline is built and warmed once, then
each rate's window is offered in turn (the cell's traffic file with its
``rate_qps`` replaced), lowest first.  Each line printed is one rate's
answered queries per second, its answer latency p50 and p90 from the due
times, and the batcher's mean batch.  The knee is the highest rate whose
``answered_qps`` is at least 90% of the rate offered and whose p90 is at
most three times the lowest rate's p90; a cell is run at four fifths of it.
"""
import argparse
import copy
import json
import sys
import time

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    root = bench_run.ROOT
    if not bench_run._paths(root):
        print("sweep: FAIL: no src/repro", file=sys.stderr)
        return 2
    from benchlib import cell as cell_lib
    from benchlib import spec as spec_lib
    from benchlib.device import DeviceError, describe, gate
    try:
        cell = spec_lib.load_cell(args.workload, root, root / "bench")
        devs = gate(cell.chips, bench_run.PLATFORM)
    except (spec_lib.SpecError, DeviceError) as e:
        print(f"sweep: FAIL: {e}", file=sys.stderr)
        return 1
    bench_run.compile_cache()
    log = cell_lib.Log()
    served = cell_lib.set_up(cell.config, cell.traffic, args.seed, log)
    rows = []
    for i, rate in enumerate(sorted(float(r) for r in args.rates.split(","))):
        traffic = copy.deepcopy(cell.traffic)
        traffic["arrival"]["rate_qps"] = rate
        w = cell_lib.run_window(cell.config, traffic, served, args.seed + i,
                                args.seconds, log)
        w.end = time.perf_counter()
        e2e = bench_run.end_to_end(w, None)
        lat = sorted(r["end"] - r["due"] for r in w.records if r["ok"])
        row = {"rate_qps": rate, "answered_qps": e2e["answered_qps"],
               "answer_p50_ms": lat[len(lat) // 2] * 1e3 if lat else None,
               "answer_p90_ms": e2e["answer_p90_ms"],
               "batch_mean": (sum(w.batch_sizes) / len(w.batch_sizes)
                              if w.batch_sizes else None),
               "queries": len(w.records),
               "unanswered": sum(not r["ok"] for r in w.records),
               "compiles_in_window": w.compiles}
        rows.append(row)
        print(json.dumps(row), flush=True)
    base = rows[0]["answer_p90_ms"]
    ok = [r["rate_qps"] for r in rows
          if r["answered_qps"] >= 0.9 * r["rate_qps"]
          and r["answer_p90_ms"] <= 3.0 * base and not r["unanswered"]]
    knee = max(ok) if ok else None
    print(json.dumps({"workload": args.workload, "knee_qps": knee,
                      "rate_at_0.8_knee": 0.8 * knee if knee else None,
                      "seconds": args.seconds, "device": describe(devs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
