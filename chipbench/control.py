#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python chipbench/control.py --workload <cell> --seconds <s> --seeds <n,n,...> [--control <k>]

In one process, for each seed: build the cell's deployment at its own size,
warm it, run a short window of the cell's own traffic, and read the compared
numbers of the program's answers (the lower readings). For the first ``k``
seeds also read them for the control: the reference one precision step
below what the configuration states, put in the program's place (the upper
readings). One JSON line per seed on standard output. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def readings(cell, seed: int, seconds: float, control: bool) -> dict:
    import check
    import loadgen
    import run

    mix = cell.mix
    dep, programs, warm = run.prepare(cell, seed)
    try:
        records, _ = loadgen.run_window(dep, mix, programs, seed, seconds)
    finally:
        dep.close()
    specs = {n: dict(s, name=n) for n, s in mix["programs"].items()}
    out = {"seed": seed, "answers": len(records),
           "program": check.readings(warm + records, dep, specs)}
    if control:
        out["control"] = check.readings(
            [r for r in warm + records if r.job.kind == "offload"], dep,
            specs, control=True)
    return out


def main(argv=None) -> int:
    import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run._place_compile_cache(run.ROOT)
    run._device(int(cell.workload["chips"]), True)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = readings(cell, seed, args.seconds, i < args.control)
        r["wall_s"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
