"""Benchmark driver: one function per paper table/figure + the roofline.

Prints ``name,us_per_call,derived`` CSV lines. Scaled-down sizes by default
(CI-friendly on 1 CPU core); pass --full for the paper's exact 256 MiB zone.
``--json`` additionally APPENDS a timestamped entry to the
``BENCH_hotpath.json`` trajectory (per-suite rows with parsed derived
metrics) — plus ``BENCH_async.json`` for the async completion-ring suite,
``BENCH_degraded.json`` for the redundancy / degraded-read suite,
``BENCH_profile.json`` for the traced fan-out profile,
``BENCH_rebuild.json`` for the self-healing recovery suite and
``BENCH_faults.json`` for the fault-injection suite when they ran — so
the perf trajectory is machine-readable across PRs (legacy single-object
files are migrated into trajectories on first write; see
``benchmarks/trajectory.py``); ``--budget SECONDS`` fails the run loudly
when it exceeds a wall-clock budget — the CI tripwire for hot-path
regressions.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

JSON_PATH = "BENCH_hotpath.json"
ASYNC_JSON_PATH = "BENCH_async.json"
DEGRADED_JSON_PATH = "BENCH_degraded.json"
PROFILE_JSON_PATH = "BENCH_profile.json"
HEALTH_JSON_PATH = "BENCH_health.json"
REBUILD_JSON_PATH = "BENCH_rebuild.json"
FAULTS_JSON_PATH = "BENCH_faults.json"


def _parse_derived(derived: str) -> dict:
    """'k1=v1;k2=v2' -> {k1: v1, ...} with numeric values parsed."""
    out: dict = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = int(v)
        except ValueError:
            try:
                out[k] = float(v.rstrip("x"))
            except ValueError:
                out[k] = v
    return out


def _row_record(row: str) -> dict:
    name, us, derived = row.split(",", 2)
    try:
        us_per_call = float(us)
    except ValueError:
        us_per_call = None            # ERROR rows keep the raw text
    return {"name": name, "us_per_call": us_per_call,
            "derived": _parse_derived(derived) if us_per_call is not None
            else {"error": derived}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-exact sizes (256 MiB zone, 5 runs)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: filter,hotpath,toolchain,"
                         "pushdown,checkpoint,paged_attn,roofline,array,"
                         "async,degraded,profile,health,rebuild,faults")
    ap.add_argument("--list", action="store_true",
                    help="print the available suite names and exit")
    ap.add_argument("--json", action="store_true",
                    help=f"write per-suite results to {JSON_PATH}")
    ap.add_argument("--budget", type=float, default=None,
                    help="fail (exit 1) if the run exceeds this many seconds")
    args = ap.parse_args()

    from benchmarks import (bench_array, bench_async, bench_checkpoint,
                            bench_degraded, bench_faults, bench_filter,
                            bench_health, bench_hotpath, bench_paged_attn,
                            bench_profile, bench_pushdown, bench_rebuild,
                            bench_toolchain, roofline, trajectory)
    from repro.runtime import place_compile_cache
    place_compile_cache()

    suites = {
        "filter": lambda: bench_filter.main(
            zone_mib=256 if args.full else 32, runs=5 if args.full else 3),
        "array": lambda: bench_array.main(
            data_mib=64 if args.full else 16, runs=5 if args.full else 3),
        "hotpath": lambda: bench_hotpath.main(
            data_mib=32 if args.full else 8, runs=5 if args.full else 3),
        "async": lambda: bench_async.main(
            data_mib=16 if args.full else 8, runs=3 if args.full else 2),
        "degraded": lambda: bench_degraded.main(
            data_mib=16 if args.full else 8, runs=5 if args.full else 3),
        "profile": lambda: bench_profile.main(
            data_mib=64 if args.full else 16, runs=5 if args.full else 3),
        "health": lambda: bench_health.main(
            data_mib=8 if args.full else 4, runs=5 if args.full else 3),
        "rebuild": lambda: bench_rebuild.main(
            data_mib=16 if args.full else 8, runs=5 if args.full else 3),
        "faults": lambda: bench_faults.main(
            data_mib=16 if args.full else 8, runs=5 if args.full else 3,
            stride=1 if args.full else 2),
        "toolchain": bench_toolchain.main,
        "pushdown": bench_pushdown.main,
        "checkpoint": bench_checkpoint.main,
        "paged_attn": bench_paged_attn.main,
        "roofline": roofline.main,
    }
    if args.list:
        for name in suites:
            print(name)
        return 0
    chosen = args.only.split(",") if args.only else list(suites)
    unknown = [n for n in chosen if n not in suites]
    if unknown:
        print(f"unknown suite(s): {', '.join(unknown)} "
              f"(try --list)", file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    print("name,us_per_call,derived")
    failures = 0
    results: dict[str, list[dict]] = {}
    for name in chosen:
        try:
            rows = suites[name]()
            for row in rows:
                print(row)
            results[name] = [_row_record(r) for r in rows]
        except Exception:
            failures += 1
            err = traceback.format_exc(limit=1)
            print(f"{name},ERROR,{err!r}")
            results[name] = [{"name": name, "us_per_call": None,
                              "derived": {"error": err}}]
    elapsed = time.perf_counter() - t0

    if args.json:
        payload = {
            "suites": results,
            "failures": failures,
            "elapsed_seconds": round(elapsed, 3),
            "full_sizes": bool(args.full),
        }
        trajectory.append_entry(JSON_PATH, payload)
        print(f"# appended to {JSON_PATH}", file=sys.stderr)
        for suite, path in (("async", ASYNC_JSON_PATH),
                            ("degraded", DEGRADED_JSON_PATH),
                            ("profile", PROFILE_JSON_PATH),
                            ("health", HEALTH_JSON_PATH),
                            ("rebuild", REBUILD_JSON_PATH),
                            ("faults", FAULTS_JSON_PATH)):
            if suite not in results:
                continue
            trajectory.append_entry(path, {"suites": {suite: results[suite]},
                                           "full_sizes": bool(args.full)})
            print(f"# appended to {path}", file=sys.stderr)

    if args.budget is not None and elapsed > args.budget:
        print(f"# BUDGET EXCEEDED: {elapsed:.1f}s > {args.budget:.1f}s "
              f"wall-clock budget — hot-path regression?", file=sys.stderr)
        return 1
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
