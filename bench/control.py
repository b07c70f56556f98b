#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip:

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 10

For each seed, in one process: the cell's set-up and a window at the cell's
own load, then every number ``correct`` compares, read from the program's
run (the lower readings) and from the controls put in the program's place
(the upper readings; ``benchlib/checks.py``).  One JSON line per seed.  The
benchmark's own runs never run the controls.
"""
import argparse
import gc
import json
import sys
from types import SimpleNamespace

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    root = bench_run.ROOT
    if not bench_run._paths(root):
        print("control: FAIL: no src/repro", file=sys.stderr)
        return 2
    from benchlib import spec as spec_lib
    from benchlib.device import DeviceError, gate, peaks_for
    try:
        cell = spec_lib.load_cell(args.workload, root, root / "bench")
        devs = gate(cell.chips, bench_run.PLATFORM)
        peaks = peaks_for(devs[0].device_kind)
    except (spec_lib.SpecError, DeviceError) as e:
        print(f"control: FAIL: {e}", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        res = bench_run.run_cell(cell, devs, peaks, SimpleNamespace(
            seed=seed, seconds=args.seconds, trace=0, trace_out="",
            control=True))
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": {k: v["value"]
                                     for k, v in res["checks"].items()},
                          "controls": res["controls"],
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"]}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
