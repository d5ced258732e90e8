#!/usr/bin/env python3
"""Runs one cell of the chip benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell, its configuration
(``chipbench/configs/<config>.json``), its traffic mix
(``chipbench/traffic/<traffic>.json``) and its metrics
(``chipbench/metrics/<metric>.py``) are found by the names in
``BENCHMARK.json``; its zones' generators (``chipbench/zones/<dist>.py``)
and its programs' kinds (``chipbench/programs/<kind>.py``) by the names in
those files. The run builds the deployment and fills its zones from
the seed, warms every shape the mix sends, measures for ``--seconds``, then
holds every answer of the window to the numpy reference.

With ``--trace 0`` it reports the cell's end-to-end metrics; with
``--trace 1`` it records a ``jax.profiler`` trace, the system's own spans and
its registry over a window of the mix's ``trace_seconds`` (at most
``--seconds``), and reports the per-layer metrics. It needs a TPU: on any
other platform, or with fewer chips than the cell asks for, it exits nonzero
and prints no result. The last line of standard output is the result as one
JSON object; the numbers compared, each beside its limit, are the last lines
of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import named  # noqa: E402

__all__ = ["Cell", "Context", "load_cell", "load_metric", "main", "run_cell"]


class NoChip(RuntimeError):
    """The platform or the chip count does not match the cell."""


@dataclass
class Cell:
    """A workload with everything its name leads to."""

    name: str
    workload: dict
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


@dataclass
class Context:
    """What a metric's reader may read. Readers return None when they find
    nothing to read."""

    records: list
    t_open: float
    t_last: float
    block_bytes: int
    setup_s: float = 0.0
    spans: list = field(default_factory=list)   # repro.telemetry.trace
    reg: dict = field(default_factory=dict)     # registry delta
    device: object = None                       # devtrace.DeviceWindow
    peaks: dict = field(default_factory=dict)


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load_json(root / cfg["file"])
    mix = _load_json(HERE / "traffic" / f"{w['traffic']}.json")
    # what the cell's zones hold and its programs mean, found before any run
    for spec in config["zones"]:
        named.zone_kind(spec)
    for spec in mix["programs"].values():
        named.program_kind(spec)

    def mine(m: dict) -> bool:
        return name in m.get("workloads", [name])

    return Cell(name, w, config, mix,
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


def load_metric(name: str):
    """The ``read(ctx)`` of ``metrics/<name>.py``."""
    return named.load("metrics", name).read


def _place_compile_cache(root: Path) -> None:
    """JAX's persistent compile cache at a fixed path inside the checkout,
    holding every program however fast it compiled, set before the first
    compile. It holds a few dozen programs, so nothing is evicted: an
    eviction-enabled cache (a size limit from the environment) keeps an
    access-time file per entry, and an entry found without one fails every
    later write."""
    import jax
    cache = root / ".jax_cache"
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _device(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and (d0.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{d0.platform} device(s) ({d0.device_kind})")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips}


def _peaks(kind: str) -> dict:
    table = _load_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def _memory_peak(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def _warm(dep, mix: dict, programs: dict) -> list:
    """Run every shape the mix sends once, through the same driver as the
    window; returns the warm-up records (their appends are checked too)."""
    import loadgen
    drv = loadgen.Driver(dep, programs, lambda name: nullcontext())
    out = []
    for job in loadgen.warm_jobs(mix, dep):
        rec = loadgen.Record(mix["tenants"][0]["name"], job)
        drv.finish(rec, drv.send(rec))
        if rec.error is not None:
            raise RuntimeError(f"warm-up {job} failed") from rec.error
        out.append(rec)
    return out


def prepare(cell: Cell, seed: int, mutate=None):
    """The cell's deployment filled from ``seed``, its programs, and its
    warm-up records, with the dispatcher running: what a window needs.
    ``mutate(dep)``, for the harness's own tests, may break the system
    under test before it is warmed."""
    import loadgen
    from deploy import build
    dep = build(cell.config, seed)
    try:
        if mutate is not None:
            mutate(dep)
        programs = {n: loadgen.program(dict(s, name=n))
                    for n, s in cell.mix["programs"].items()}
        if dep.config["entry"] == "scheduler":
            dep.entry.start()
        t0 = time.perf_counter()
        warm = _warm(dep, cell.mix, programs)
        dep.seconds["warm"] = time.perf_counter() - t0
    except BaseException:
        dep.close()
        raise
    return dep, programs, warm


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, mutate=None) -> dict:
    """One run of ``cell``; returns the result object. ``mutate(dep)``, for
    the harness's own tests, may break the system under test (see
    :func:`prepare`)."""
    import check
    import loadgen

    if require_tpu:
        _place_compile_cache(ROOT)
    device = _device(int(cell.workload["chips"]), require_tpu)
    peaks = _peaks(device["kind"]) if require_tpu else {}
    mix = cell.mix
    t_device = time.perf_counter()
    dep, programs, warm = prepare(cell, seed, mutate)
    phases = {"to_device": t_device - T_START, **dep.seconds}
    try:
        window = min(seconds, float(mix.get("trace_seconds", seconds))) \
            if trace else seconds
        setup_s = time.perf_counter() - T_START
        if trace:
            ctx_extra, (records, t_open) = _traced(
                lambda ann: loadgen.run_window(dep, mix, programs, seed,
                                               window, ann))
        else:
            ctx_extra = {}
            records, t_open = loadgen.run_window(dep, mix, programs, seed,
                                                 window)
        device["memory_peak_bytes"] = _memory_peak(device["count"])
    finally:
        dep.close()
    misses = sum(r.cache_misses for r in records)
    if misses:
        raise RuntimeError(f"{misses} offloads in the window missed the "
                           f"compile cache: warm-up left a shape out")
    done = [r.t_done for r in records if r.ok]
    ctx = Context(records, t_open, max(done) if done else t_open,
                  dep.block_bytes, setup_s=setup_s, peaks=peaks, **ctx_extra)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_metric(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if trace:
        dw = ctx.device
        device["busy_s"] = dw.busy_s
        device["window_s"] = dw.window_s
    specs = {n: dict(s, name=n) for n, s in mix["programs"].items()}
    numbers = check.readings(warm + records, dep, specs)
    correct, shown = check.judge(numbers, mix["limits"])
    result = {"correct": correct, "attempted": len(records),
              "failed": sum(1 for r in records if not r.ok),
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {
            "device_ops": [[n, t] for n, t in ctx.device.ops],
            "idle_gaps": [[n, t] for n, t in ctx.device.gaps]}
    result["setup_phases_s"] = phases
    result["checks"] = shown
    return result


def _traced(run):
    """Run ``run(annotate)`` under the profiler, the system's spans and a
    registry delta; returns the context fields and ``run``'s value."""
    import jax
    import devtrace
    from repro.telemetry import trace as spans
    from repro.telemetry.metrics import registry
    d = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        spans.clear()
        before = registry().snapshot()
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            spans.set_enabled(True)
            with jax.profiler.TraceAnnotation(devtrace.WINDOW_ANNOTATION):
                value = run(jax.profiler.TraceAnnotation)
        finally:
            spans.set_enabled(False)
            jax.profiler.stop_trace()
        reg = registry().delta(before)
        events = spans.drain()
        spans.clear()
        pb = sorted(Path(d).glob("plugins/profile/*/*.xplane.pb"))
        if not pb:
            raise RuntimeError("the profiler wrote no trace")
        dw = devtrace.reduce_trace(str(pb[-1]))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return {"spans": events, "reg": reg, "device": dw}, value


def _report(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chipbench: no system under test at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    _report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
