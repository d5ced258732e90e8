"""zcsd-top: a live terminal dashboard over the telemetry stack.

Renders the operator's view of an emulated array the way ``iostat``/``ztop``
would: per-member SMART health, per-tenant QoS (bytes / ops / p50 / p99 /
degraded reads, straight off the global registry's ``tenant.*`` series),
currently-active alerts, rebuild/scrub progress (per-seat progress bars off
the :class:`~repro.array.rebuild.ArrayManager` plus the ``scrub.*``
counters), and the tail of the structured event log — one refreshing frame
per interval.

The renderer is a pure function (:func:`render`) over whatever monitors /
engine / log the caller hands it, so tests can assert on a frame without a
terminal. Run as a script it drives a demo workload — a two-member raid1
array serving two tenants, with a member zone killed partway through — so
every pane has something to show::

    PYTHONPATH=src python examples/top.py              # live, ctrl-C to quit
    PYTHONPATH=src python examples/top.py --once       # single frame (CI)
"""
from __future__ import annotations

import argparse
import sys
import threading
import time

import numpy as np

from repro.telemetry import (
    AlertEngine,
    ArrayHealthMonitor,
    ErrorRateRule,
    HealthPromotionRule,
    TenantLatencySLORule,
    event_log,
    registry,
    retry_storm_rule,
)

_STATUS_GLYPH = {"HEALTHY": "ok", "SUSPECT": "??", "DEGRADED": "!!",
                 "OFFLINE": "XX"}


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}GiB"


def tenant_rows(snapshot: dict) -> list[dict]:
    """Pull ``{tenant, ops, bytes, errors, degraded, p50_s, p99_s}`` rows
    out of a registry snapshot's ``tenant.*`` series."""
    tenants = sorted({k.split(".")[1] for k in snapshot
                      if k.startswith("tenant.") and k.count(".") >= 2})
    rows = []
    for t in tenants:
        pfx = f"tenant.{t}."
        ops = snapshot.get(pfx + "ops", 0)
        if not ops:
            continue                    # registered but idle: keep the pane quiet
        rows.append({
            "tenant": t,
            "ops": ops,
            "bytes": snapshot.get(pfx + "bytes", 0),
            "errors": snapshot.get(pfx + "errors", 0),
            "degraded": snapshot.get(pfx + "degraded_reads", 0),
            "p50_s": snapshot.get(pfx + "offload_latency_seconds.p50", 0.0),
            "p99_s": snapshot.get(pfx + "offload_latency_seconds.p99", 0.0),
        })
    return rows


def render(*, monitor: ArrayHealthMonitor | None = None,
           engine: AlertEngine | None = None, manager=None,
           log=None, snapshot: dict | None = None, events_tail: int = 8,
           width: int = 78) -> str:
    """One dashboard frame as a string (no terminal control codes)."""
    snap = snapshot if snapshot is not None else registry().snapshot()
    log = log if log is not None else event_log()
    bar = "=" * width
    thin = "-" * width
    lines = [bar,
             f"zcsd-top  {time.strftime('%H:%M:%S')}   "
             f"events={len(log)} (dropped={log.dropped})",
             bar]

    lines.append("POOL HEALTH")
    if monitor is not None:
        lines.append(f"  {'member':<18}{'status':<10}{'zones':>6}"
                     f"{'off/ro':>8}{'errs':>6}{'outliers':>9}"
                     f"{'read p99':>10}")
        for smart in monitor.smart_logs():
            glyph = _STATUS_GLYPH.get(smart["status"], "?")
            lines.append(
                f"  {smart['device']:<18}"
                f"{glyph + ' ' + smart['status']:<10}"
                f"{smart['zones']:>6}"
                f"{str(smart['zones_offline']) + '/' + str(smart['zones_read_only']):>8}"
                f"{smart['media_errors']:>6}"
                f"{smart['latency_outliers']:>9}"
                f"{smart['read_p99_s'] * 1e6:>9.0f}u")
    else:
        lines.append("  (no array monitor attached)")
    lines.append(thin)

    lines.append("FAULTS")
    smarts = monitor.smart_logs() if monitor is not None else []
    if any(s.get("faults_injected") or s.get("retries")
           or s.get("io_timeouts") for s in smarts):
        lines.append(f"  {'member':<18}{'injected':>9}{'retried':>9}"
                     f"{'timed-out':>10}{'exhausted':>10}")
        for smart in smarts:
            lines.append(
                f"  {smart['device']:<18}"
                f"{smart.get('faults_injected', 0):>9}"
                f"{smart.get('retries', 0):>9}"
                f"{smart.get('io_timeouts', 0):>10}"
                f"{smart['media_errors']:>10}")
    else:
        lines.append("  (no faults injected)")
    lines.append(thin)

    lines.append("TENANTS")
    rows = tenant_rows(snap)
    if rows:
        lines.append(f"  {'tenant':<12}{'ops':>8}{'bytes':>10}{'errs':>6}"
                     f"{'degraded':>9}{'p50':>10}{'p99':>10}")
        for r in rows:
            lines.append(
                f"  {r['tenant']:<12}{r['ops']:>8}"
                f"{_fmt_bytes(r['bytes']):>10}{r['errors']:>6}"
                f"{r['degraded']:>9}"
                f"{r['p50_s'] * 1e3:>8.2f}ms"
                f"{r['p99_s'] * 1e3:>8.2f}ms")
    else:
        lines.append("  (no tenant traffic yet)")
    lines.append(thin)

    lines.append("ALERTS")
    active = {r: keys for r, keys in (engine.active() if engine else {}).items()
              if keys}
    if active:
        for rule, keys in sorted(active.items()):
            for key in sorted(keys):
                lines.append(f"  FIRING  {rule:<18} {key}")
    else:
        lines.append("  (none firing)")
    if engine is not None and engine.fired:
        last = engine.fired[-1]
        lines.append(f"  last: [{last.severity.name}] {last.message[:width - 10]}")
    lines.append(thin)

    lines.append("REBUILD / SCRUB")
    seats = manager.status() if manager is not None else {}
    if seats:
        for member, st in sorted(seats.items()):
            total = st.get("zones_total", 0)
            done = st.get("zones_done", 0)
            frac = done / total if total else 0.0
            fill = int(round(frac * 20))
            bar_s = "#" * fill + "." * (20 - fill)
            lines.append(
                f"  member {member} -> spare dev{st.get('spare', '?')}  "
                f"{st.get('state', '?'):<9}[{bar_s}] {done}/{total} zones"
                + (f"  restarts={st['restarts']}" if st.get("restarts") else "")
                + (f"  failed={st['zones_failed']}"
                   if st.get("zones_failed") else ""))
    elif manager is not None:
        lines.append("  (no rebuild has run)")
    if manager is not None:
        lines.append(
            f"  spares available: {manager.spare_count}   scrub: "
            f"passes={snap.get('scrub.passes', 0)} "
            f"rows={snap.get('scrub.rows_verified', 0)} "
            f"mismatches={snap.get('scrub.mismatches', 0)}")
    else:
        lines.append("  (no array manager attached)")
    lines.append(thin)

    lines.append(f"EVENTS (last {events_tail})")
    tail = log.tail(events_tail)
    if tail:
        for e in tail:
            lines.append(f"  {e.seq:>5} [{e.severity.name:<8}] "
                         f"{e.name:<22} {e.message[:width - 42]}")
    else:
        lines.append("  (event log empty)")
    lines.append(bar)
    return "\n".join(lines)


# ----------------------------------------------------------- demo workload
def _demo(stop: threading.Event):
    """Two tenants hammering a raid1 pair; a member dies mid-run and the
    self-healing manager rebuilds it onto a hot spare while a background
    scrub ticks. Returns (monitor, engine, manager, thread)."""
    from repro.array import ArrayManager, OffloadScheduler, StripedZoneArray
    from repro.core import filter_count
    from repro.faults import FaultInjector, FaultSpec, RetryPolicy
    from repro.zns import ZonedDevice

    data_bytes = 2 * 1024 * 1024
    rng = np.random.default_rng(0)
    data = rng.integers(0, 2**31 - 1, data_bytes // 4, dtype=np.int32)
    devices = [ZonedDevice(num_zones=4, zone_bytes=data_bytes,
                           block_bytes=4096, read_us_per_block=1.0)
               for _ in range(2)]
    array = StripedZoneArray(devices, stripe_blocks=64, redundancy="raid1")
    array.zone_append(0, data)
    # transient media errors on the datapath, absorbed by bounded retries —
    # feeds the FAULTS pane and the retry-storm alert without ejecting anyone
    injector = FaultInjector(7, FaultSpec(read_error_rate=0.02))
    injector.attach_array(array, policy=RetryPolicy(max_attempts=4,
                                                    backoff_base_s=50e-6))
    program = filter_count("int32", "gt", 2**30)

    monitor = ArrayHealthMonitor(array)
    monitor.register_on(registry())
    engine = AlertEngine(rules=[
        HealthPromotionRule(monitor),
        ErrorRateRule(pattern="health.*_errors"),
        retry_storm_rule(max_per_second=5.0),
        TenantLatencySLORule(0.5),
    ])
    spare = ZonedDevice(num_zones=4, zone_bytes=data_bytes, block_bytes=4096,
                        append_us_per_block=20.0)   # paced: progress visible
    manager = ArrayManager(array, spares=[spare], monitor=monitor)
    manager.attach(engine)
    manager.start_scrub(interval=2.0)

    def loop():
        sched = OffloadScheduler(array)
        sched.register_tenant("alice", weight=3)
        sched.register_tenant("bob", weight=1)
        n = 0
        with sched:
            while not stop.is_set():
                sched.nvm_cmd_bpf_run(program, 0,
                                      tenant="alice" if n % 4 else "bob")
                n += 1
                if n == 12:             # fault injection: past the DEGRADED
                    array.set_offline(0, device=1)  # threshold (2/4 zones),
                    array.set_offline(1, device=1)  # so promotion fires
                stop.wait(0.05)

    t = threading.Thread(target=loop, name="top-demo", daemon=True)
    t.start()
    return monitor, engine, manager, t


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--interval", type=float, default=1.0,
                    help="refresh interval seconds")
    ap.add_argument("--frames", type=int, default=0,
                    help="stop after N frames (0 = until ctrl-C)")
    ap.add_argument("--once", action="store_true",
                    help="render a single frame and exit")
    args = ap.parse_args(argv)
    if args.once:
        args.frames = 1

    stop = threading.Event()
    monitor, engine, manager, worker = _demo(stop)
    frames = 0
    try:
        while True:
            time.sleep(0.0 if args.once else args.interval)
            engine.evaluate()           # doubles as the SMART sampling tick
            frame = render(monitor=monitor, engine=engine, manager=manager)
            if not args.once:
                sys.stdout.write("\x1b[2J\x1b[H")   # clear + home
            print(frame, flush=True)
            frames += 1
            if args.frames and frames >= args.frames:
                return 0
    except KeyboardInterrupt:
        return 0
    finally:
        stop.set()
        manager.stop()
        worker.join(timeout=5.0)


if __name__ == "__main__":
    sys.exit(main())
