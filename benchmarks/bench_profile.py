"""Traced array fan-out profile: name the serialization point, don't guess.

The original thread-per-member fan-out stopped scaling past 2 devices
(675 -> 1153 -> 979 -> 760 MiB/s at 1/2/4/8) and this profile named the
culprit: ``worker.compute`` — N GIL-contending per-worker JAX dispatches —
blew up ~60x on the straggler's critical path. The ISSUE-10 pipeline
(read stage -> ONE array-wide batched dispatch -> gather-pool combine)
removed that axis entirely, and this profile now attributes the staged
offload wall clock so any NEW serialization point gets a name:

  * per width, every ``offload.execute`` span is decomposed into its
    sequential dispatcher phases (``offload.plan`` / ``offload.stage.read``
    / ``offload.stage.compute`` / ``offload.stage.combine`` — asserted to
    cover >= 90% of the measured wall, so the attribution is honest, not
    vibes);
  * inside the compute phase, the dispatcher-side children split the story:
    ``stage.read_wait`` (blocked on ring completions + staging memcpys —
    the number that grows if the pipeline serializes on I/O),
    ``stage.dispatch`` (the single batched compiled call per group) and
    ``stage.serve_chunk`` (individually re-served tail/degraded chunks);
    ``offload.stage.combine`` is the rendezvous with the gather-pool
    combiner, which absorbs the trailing group's XLA materialization;
  * the dominant serialization point is the largest critical-path component
    that FAILED to shrink with width (seconds at max width >= half its
    1-device seconds) — reported by name in the diagnosis row, which the
    refactor must keep AWAY from the old per-worker-compute shape;
  * a tracing-overhead tripwire measures the DISABLED-path primitive costs
    (no-op span, counter inc, histogram observe, enabled check) and asserts
    the per-offload instrumentation budget stays under 3% of a measured
    single-device offload — the "observability must not slow the hot path"
    contract, enforced in bench-smoke.
"""
from __future__ import annotations

import time

import numpy as np

from repro.array import OffloadScheduler, StripedZoneArray
from repro.core import CsdTier, NvmCsd, filter_count
from repro.telemetry import trace as _trace
from repro.telemetry.metrics import MetricsRegistry, registry as _registry
from repro.zns import ZonedDevice

RAND_MAX = 2**31 - 1
BLOCK = 4096

# phase coverage the attribution must reach before we trust the diagnosis
MIN_ATTRIBUTION = 0.90
# disabled-tracing overhead budget on the single-device offload row
MAX_DISABLED_OVERHEAD = 0.03

# critical-path components that can be "the serialization point" (everything
# dispatcher-serial plus the staged read wait itself — if stage.read_wait
# still dominates at max width the reads are NOT overlapping and that IS
# the finding)
_CP_COMPONENTS = ("stage.read_wait", "stage.dispatch", "stage.serve_chunk",
                  "offload.plan", "offload.stage.read",
                  "offload.stage.combine")


def _spans(events: list[dict], name: str) -> list[dict]:
    return [e for e in events if e["type"] == "span" and e["name"] == name]


def _children(events: list[dict], parent: dict, name: str,
              same_tid: bool = False) -> list[dict]:
    lo = parent["ts"] - 1e-9
    hi = parent["ts"] + parent["dur"] + 1e-6
    out = []
    for e in _spans(events, name):
        if e["ts"] >= lo and e["ts"] + e["dur"] <= hi:
            if same_tid and e["tid"] != parent["tid"]:
                continue
            out.append(e)
    return out


def _critical_path(events: list[dict], execute: dict) -> dict:
    """Decompose ONE offload.execute span into named critical-path seconds.

    plan / stage.read / stage.compute / stage.combine are the sequential
    phases of the ONE dispatcher thread (the pipeline has no per-member
    workers to straggle); inside the compute phase its read_wait / dispatch
    / serve_chunk children split the time. The residuals get their own
    names (compute.other, execute.other) so every second of the wall is
    accounted somewhere. ``stage.copy`` is the offload's staging memcpy in
    gather-pool seconds, off the critical path and outside the coverage."""
    cp = {c: 0.0 for c in _CP_COMPONENTS}
    cp.update({"compute.other": 0.0, "execute.other": 0.0})
    oid = execute["tags"].get("offload")
    cp["stage.copy"] = sum(e["dur"] for e in _spans(events, "stage.copy")
                           if e["tags"].get("offload") == oid)
    plan = sum(e["dur"] for e in _children(events, execute, "offload.plan"))
    read = sum(e["dur"]
               for e in _children(events, execute, "offload.stage.read"))
    combine = sum(e["dur"] for e in
                  _children(events, execute, "offload.stage.combine"))
    computes = _children(events, execute, "offload.stage.compute")
    compute = sum(e["dur"] for e in computes)
    cp["offload.plan"] = plan
    cp["offload.stage.read"] = read
    cp["offload.stage.combine"] = combine
    inner = 0.0
    for ph in computes:
        for nm in ("stage.read_wait", "stage.dispatch", "stage.serve_chunk"):
            s = sum(e["dur"] for e in
                    _children(events, ph, nm, same_tid=True))
            cp[nm] += s
            inner += s
    cp["compute.other"] = max(compute - inner, 0.0)
    cp["execute.other"] = max(
        execute["dur"] - plan - read - compute - combine, 0.0)
    cp["_phase_coverage"] = (plan + read + compute + combine) \
        / execute["dur"] if execute["dur"] > 0 else 1.0
    return cp


def run_profile(
    *,
    widths: tuple[int, ...] = (1, 2, 4, 8),
    data_mib: int = 16,
    stripe_blocks: int = 64,
    read_us_per_block: float = 16.0,
    runs: int = 3,
    seed: int = 0,
) -> list[dict]:
    """bench_array's fan-out, re-run under tracing, with per-component
    wall-time attribution per width."""
    data_bytes = data_mib * 1024 * 1024
    rng = np.random.default_rng(seed)
    data = rng.integers(0, RAND_MAX, data_bytes // 4, dtype=np.int32)
    expected = int((data > RAND_MAX // 2).sum())
    program = filter_count("int32", "gt", RAND_MAX // 2)

    out: list[dict] = []
    for n in widths:
        devices = [
            ZonedDevice(num_zones=1, zone_bytes=data_bytes, block_bytes=BLOCK,
                        read_us_per_block=read_us_per_block)
            for _ in range(n)
        ]
        with StripedZoneArray(devices, stripe_blocks=stripe_blocks) as array:
            array.zone_append(0, data)
            with OffloadScheduler(array) as sched:
                sched.nvm_cmd_bpf_run(program, 0)   # warm-up pays the JIT
                gather0 = _registry().snapshot()
                _trace.clear()
                times = []
                with _trace.tracing(True):
                    for _ in range(runs):
                        t = time.perf_counter()
                        sched.nvm_cmd_bpf_run(program, 0)
                        times.append(time.perf_counter() - t)
                assert int(sched.nvm_cmd_bpf_result()) == expected
                events = _trace.drain()
                gather_delta = _registry().delta(gather0)
            dev_read_s = sum(
                d.metrics.snapshot().get("read.service_seconds.sum", 0.0)
                for d in devices)

        executes = _spans(events, "offload.execute")
        assert len(executes) >= runs, (
            f"traced {len(executes)} offload.execute spans for {runs} runs — "
            "tracing lost the measured offloads")
        # take the LAST `runs` executes (warm-up ran before clear(), but be
        # defensive about any stray command)
        executes = sorted(executes, key=lambda e: e["ts"])[-runs:]
        agg: dict[str, float] = {}
        coverage = []
        for ex in executes:
            cp = _critical_path(events, ex)
            coverage.append(cp.pop("_phase_coverage"))
            for k, v in cp.items():
                agg[k] = agg.get(k, 0.0) + v
        execute_wall = sum(e["dur"] for e in executes)
        attributed = min(coverage)
        assert attributed >= MIN_ATTRIBUTION, (
            f"phase attribution covers only {attributed:.0%} of the "
            f"{n}-device offload wall (need >= {MIN_ATTRIBUTION:.0%}) — "
            "a phase span went missing")
        seconds = float(np.mean(times))
        out.append({
            "devices": n,
            "seconds": seconds,
            "mib_per_s": data_mib / seconds,
            "execute_wall_seconds": execute_wall,
            "attributed": attributed,
            "critical_path_seconds": {k: round(v, 6)
                                      for k, v in agg.items()},
            "dev_read_service_seconds": dev_read_s,
            "gather_queue_wait_seconds":
                gather_delta.get("gather.queue_wait_seconds.sum", 0.0),
            "trace_events": len(events),
            "trace_dropped": _trace.dropped(),
        })
        _trace.clear()
    return out


def diagnose(results: list[dict]) -> dict:
    """Name the dominant serialization point: the largest critical-path
    component at max width that failed to shrink with the device count."""
    first, last = results[0], results[-1]
    cp1 = first["critical_path_seconds"]
    cpN = last["critical_path_seconds"]
    candidates = {}
    for c in _CP_COMPONENTS:
        s1, sN = cp1.get(c, 0.0), cpN.get(c, 0.0)
        scaling = sN / s1 if s1 > 0 else float("inf") if sN > 0 else 0.0
        candidates[c] = {"w1_seconds": s1, "wmax_seconds": sN,
                         "scaling": scaling}
    non_scaling = {c: v for c, v in candidates.items()
                   if v["wmax_seconds"] > 0 and v["scaling"] >= 0.5}
    pool = non_scaling or candidates
    top = max(pool, key=lambda c: pool[c]["wmax_seconds"])
    return {"top_serialization_point": top,
            "widths": (first["devices"], last["devices"]),
            "components": candidates}


def measure_overhead(data_mib: int = 4, runs: int = 3) -> dict:
    """Disabled-path instrumentation budget vs a measured offload.

    There is no uninstrumented build to diff against, so the tripwire is a
    deterministic primitive-cost bound: time each disabled primitive (no-op
    span, counter inc, histogram observe, enabled check), charge the hot
    path DOUBLE its actual per-offload primitive count as safety margin,
    and require the total under 3% of a real single-device offload."""
    assert not _trace.enabled()
    n = 200_000

    t0 = time.perf_counter()
    for _ in range(n):
        with _trace.span("ovh"):
            pass
    span_s = (time.perf_counter() - t0) / n

    t0 = time.perf_counter()
    for _ in range(n):
        _trace.enabled()
    enabled_s = (time.perf_counter() - t0) / n

    reg = MetricsRegistry("bench_overhead")
    c = reg.counter("c")
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc()
    inc_s = (time.perf_counter() - t0) / n

    h = reg.histogram("h")
    t0 = time.perf_counter()
    for _ in range(n):
        h.observe(1e-4)
    observe_s = (time.perf_counter() - t0) / n

    # single-device JIT offload per call: 2 tier spans, 2 device histogram
    # observes, 2 counter incs, 1 enabled check — charged at 2x
    per_offload = 2 * (2 * span_s + 2 * observe_s + 2 * inc_s + enabled_s)

    data_bytes = data_mib * 1024 * 1024
    dev = ZonedDevice(num_zones=1, zone_bytes=data_bytes, block_bytes=BLOCK)
    rng = np.random.default_rng(0)
    dev.zone_append(0, rng.integers(0, RAND_MAX, data_bytes // 4,
                                    dtype=np.int32))
    csd = NvmCsd(dev)
    program = filter_count("int32", "gt", RAND_MAX // 2)
    csd.nvm_cmd_bpf_run(program, 0, tier=CsdTier.JIT)   # warm-up
    times = []
    for _ in range(runs):
        t = time.perf_counter()
        csd.nvm_cmd_bpf_run(program, 0, tier=CsdTier.JIT)
        times.append(time.perf_counter() - t)
    read_row_s = float(np.mean(times))
    ratio = per_offload / read_row_s
    assert ratio < MAX_DISABLED_OVERHEAD, (
        f"disabled-tracing overhead {ratio:.2%} of the read row exceeds the "
        f"{MAX_DISABLED_OVERHEAD:.0%} budget (noop span {span_s * 1e9:.0f}ns, "
        f"inc {inc_s * 1e9:.0f}ns, observe {observe_s * 1e9:.0f}ns)")
    return {"noop_span_ns": span_s * 1e9, "enabled_ns": enabled_s * 1e9,
            "counter_inc_ns": inc_s * 1e9, "observe_ns": observe_s * 1e9,
            "per_offload_overhead_us": per_offload * 1e6,
            "read_row_us": read_row_s * 1e6, "ratio": ratio}


def main(data_mib: int = 16, runs: int = 3) -> list[str]:
    rows = []
    results = run_profile(data_mib=data_mib, runs=runs)
    for r in results:
        cp = r["critical_path_seconds"]
        rows.append(
            f"profile_{r['devices']}dev,{r['seconds'] * 1e6:.0f},"
            f"mib_per_s={r['mib_per_s']:.1f};attributed={r['attributed']:.2f};"
            f"read_wait_ms={cp.get('stage.read_wait', 0) * 1e3:.1f};"
            f"staging_ms={cp.get('stage.copy', 0) * 1e3:.1f};"
            f"dispatch_ms={cp.get('stage.dispatch', 0) * 1e3:.1f};"
            f"serve_ms={cp.get('stage.serve_chunk', 0) * 1e3:.1f};"
            f"submit_ms={cp.get('offload.stage.read', 0) * 1e3:.1f};"
            f"combine_ms={cp.get('offload.stage.combine', 0) * 1e3:.1f};"
            f"plan_ms={cp.get('offload.plan', 0) * 1e3:.1f};"
            f"events={r['trace_events']};dropped={r['trace_dropped']}"
        )
    diag = diagnose(results)
    top = diag["top_serialization_point"]
    comp = diag["components"][top]
    rows.append(
        f"profile_diagnosis,0,"
        f"top_serialization_point={top};"
        f"w1_ms={comp['w1_seconds'] * 1e3:.1f};"
        f"wmax_ms={comp['wmax_seconds'] * 1e3:.1f};"
        f"scaling={comp['scaling']:.2f}x;"
        f"widths={diag['widths'][0]}-{diag['widths'][1]}"
    )
    o = measure_overhead()
    rows.append(
        f"profile_overhead,{o['per_offload_overhead_us']:.3f},"
        f"ratio={o['ratio']:.4f};noop_span_ns={o['noop_span_ns']:.0f};"
        f"counter_inc_ns={o['counter_inc_ns']:.0f};"
        f"observe_ns={o['observe_ns']:.0f};"
        f"read_row_us={o['read_row_us']:.0f}"
    )
    return rows


if __name__ == "__main__":
    for row in main(data_mib=16, runs=3):
        print(row)
