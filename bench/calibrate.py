#!/usr/bin/env python3
"""Readings behind the limits of a cell's correctness check.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--out chiprun_out/calibrate.jsonl]

One process sets the cell up once, then for each seed runs one call of the
timed path at the cell's own size and the check's comparison (the program's
readings, whose largest sets the lower reading), and for each control seed
the comparison with the reference in the next lower precision put in the
program's place (whose smallest sets the upper reading).  Each reading is
one JSON line.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if sys.path and Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path.pop(0)
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH.parent)]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    wl, cfg = run.load_cell(args.workload)
    try:
        devices = run.check_devices(int(wl["chips"]))
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    entry = run.load_module("entries", wl["entry"])
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        rec["workload"] = args.workload
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    t0 = time.perf_counter()
    state = entry.setup(cfg, wl, seeds[0] if seeds else 0, devices)
    emit({"kind": "setup", "seconds": time.perf_counter() - t0})
    program = getattr(entry, "calibration", entry.check)
    for kind, seed_list, fn in (("program", seeds, program),
                                ("control", control_seeds, entry.control)):
        for seed in seed_list:
            if hasattr(entry, "reseed"):
                entry.reseed(state, seed)
            t1 = time.perf_counter()
            rec = entry.call(state, 0, run.call_seed(seed, 0))
            t2 = time.perf_counter()
            readings = fn(state, [rec], seed)
            emit({"kind": kind, "seed": seed, "call_s": t2 - t1,
                  "check_s": time.perf_counter() - t2, "readings": readings})
    return 0


if __name__ == "__main__":
    sys.exit(main())
