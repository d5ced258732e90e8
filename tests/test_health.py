"""Health telemetry: event log, SMART monitors, alert engine, instrumentation.

The layer tests pin the contracts — bounded event-log memory, exact
counts under get-or-create races, the HEALTHY→SUSPECT→DEGRADED→OFFLINE
state walk, edge-triggered alerting — on private registries/logs so
nothing leaks between tests. ``TestLiveArrayPipeline`` drives the whole
chain on a live raid1 array under two tenants' offloads.
"""
from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from repro.array import OffloadScheduler, StripedZoneArray
from repro.core import filter_count
from repro.telemetry.alerts import (AlertEngine, ErrorRateRule,
                                    HealthPromotionRule, TenantLatencySLORule)
from repro.telemetry.events import EventLog, Severity, event_log
from repro.telemetry.health import (ArrayHealthMonitor, DeviceHealthMonitor,
                                    HealthStatus)
from repro.telemetry.metrics import MetricsRegistry, registry
from repro.zns import ZNSError, ZonedDevice
from repro.zns.device import ZoneStateError


# -------------------------------------------------------------- event log
class TestEventLog:
    def test_publish_filter_and_since_seq(self):
        log = EventLog()
        log.publish("zone.offline", severity=Severity.ERROR, zone=3)
        log.publish("zone.read_only", severity=Severity.WARNING)
        log.publish("health.status", severity=Severity.INFO)
        assert len(log.snapshot(name="zone")) == 2       # dotted prefix
        assert len(log.snapshot(name="zone.offline")) == 1
        assert len(log.snapshot(min_severity=Severity.ERROR)) == 1
        seq = log.snapshot(name="zone.read_only")[0].seq
        later = log.snapshot(since_seq=seq)
        assert [e.name for e in later] == ["health.status"]
        assert log.snapshot(name="zone.offline")[0].tags["zone"] == 3

    def test_bounded_memory_under_sustained_publishing(self, tmp_path):
        """The ring is a CQ: sustained publishing overwrites the oldest
        entries and counts the loss — memory never grows past capacity."""
        log = EventLog(capacity=256)
        n = 10_000
        for i in range(n):
            log.publish("flood", severity=Severity.DEBUG, i=i)
        assert len(log) == 256
        assert log.published == n
        assert log.dropped == n - 256
        tail = log.snapshot()
        # the survivors are exactly the newest 256, in order
        assert [e.tags["i"] for e in tail] == list(range(n - 256, n))
        path = tmp_path / "events.jsonl"
        assert log.export_jsonl(str(path)) == 256
        assert len(path.read_text().splitlines()) == 256

    def test_export_jsonl_round_trips(self, tmp_path):
        log = EventLog()
        log.publish("a.b", severity=Severity.WARNING, message="hi", k=1)
        path = tmp_path / "events.jsonl"
        assert log.export_jsonl(str(path)) == 1
        rec = json.loads(path.read_text())
        assert rec["name"] == "a.b"
        assert rec["severity"] == "WARNING"
        assert rec["tags"] == {"k": 1}

    def test_subscriber_errors_are_swallowed_and_unsubscribe_works(self):
        log = EventLog()
        seen: list[str] = []
        log.subscribe(lambda e: 1 / 0)           # must not break publish
        unsub = log.subscribe(lambda e: seen.append(e.name))
        log.publish("x", severity=Severity.INFO)
        unsub()
        log.publish("y", severity=Severity.INFO)
        assert seen == ["x"]

    def test_concurrent_publishers_exact_accounting(self):
        log = EventLog(capacity=128)
        n_threads, per_thread = 8, 2000
        start = threading.Barrier(n_threads)

        def work():
            start.wait()
            for _ in range(per_thread):
                log.publish("race", severity=Severity.DEBUG)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = n_threads * per_thread
        assert log.published == total
        assert log.dropped == total - 128
        assert len(log) == 128


# ----------------------------------------------------- tenant series races
class TestTenantSeriesRace:
    def test_get_or_create_same_histogram_exact_counts(self):
        """8 threads race the first touch of one ``tenant.*`` histogram:
        everyone must land on the SAME object and no observation may be
        lost — the property the per-tenant accounting path relies on."""
        reg = MetricsRegistry("race")
        n_threads, per_thread = 8, 5000
        start = threading.Barrier(n_threads)
        got: list = [None] * n_threads

        def work(i: int):
            start.wait()
            h = reg.histogram("tenant.alice.offload_latency_seconds")
            got[i] = h
            for j in range(per_thread):
                h.observe(1e-5 * (1 + j % 5))
                reg.counter("tenant.alice.ops").inc()

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(h is got[0] for h in got)
        assert got[0].count == n_threads * per_thread
        assert reg.counter("tenant.alice.ops").value == n_threads * per_thread


# ------------------------------------------------- device instrumentation
class TestDeviceHealthCounters:
    def test_zone_death_counts_and_publishes(self):
        log = event_log()
        seq0 = log.last_seq()
        dev = ZonedDevice(num_zones=2, zone_bytes=1 << 20, block_bytes=4096)
        dev.set_read_only(0)
        dev.set_offline(1)
        snap = dev.metrics.snapshot()
        assert snap["zone_readonly_transitions"] == 1
        assert snap["zone_offline_transitions"] == 1
        names = [e.name for e in log.snapshot(since_seq=seq0)]
        assert "zone.read_only" in names and "zone.offline" in names
        # idempotent kill: no double-count, no duplicate event
        dev.set_offline(1)
        assert dev.metrics.snapshot()["zone_offline_transitions"] == 1

    def test_read_errors_counter_moves_on_failed_read(self):
        dev = ZonedDevice(num_zones=1, zone_bytes=1 << 20, block_bytes=4096)
        dev.zone_append(0, np.arange(4096 // 4, dtype=np.int32))
        dev.set_offline(0)
        with pytest.raises(ZoneStateError):
            dev.read_blocks(0, 0, 1)
        assert dev.stats["read_errors"] == 1


# --------------------------------------------------------- health monitor
class _FakeState:
    def __init__(self, value: str):
        self.value = value


class _FakeZone:
    def __init__(self, state: str):
        self.state = _FakeState(state)


class _FakeDevice:
    """Duck-typed device: lets tests drive latency windows synthetically."""

    dev_ordinal = 99

    def __init__(self, n_zones: int = 2):
        self.metrics = MetricsRegistry("fake")
        self.states = ["empty"] * n_zones

    def report_zones(self):
        return [_FakeZone(s) for s in self.states]


class TestDeviceHealthMonitor:
    def test_zone_state_escalation_walk(self):
        log = EventLog()
        dev = ZonedDevice(num_zones=4, zone_bytes=1 << 20, block_bytes=4096)
        mon = DeviceHealthMonitor(dev, events=log, name="m0")
        assert mon.sample() is HealthStatus.HEALTHY
        dev.set_offline(0)                      # 1/4 offline: visibly wrong
        assert mon.sample() is HealthStatus.SUSPECT
        dev.set_offline(1)                      # 2/4 >= 0.5 fraction
        assert mon.sample() is HealthStatus.DEGRADED
        dev.set_offline(2)
        dev.set_offline(3)                      # all gone
        assert mon.sample() is HealthStatus.OFFLINE
        walk = [(e.tags["from_status"], e.tags["to_status"])
                for e in log.snapshot(name="health.status")]
        assert walk == [("HEALTHY", "SUSPECT"), ("SUSPECT", "DEGRADED"),
                        ("DEGRADED", "OFFLINE")]

    def test_latency_outlier_detection_and_recovery(self):
        log = EventLog()
        dev = _FakeDevice()
        mon = DeviceHealthMonitor(dev, events=log, name="m0",
                                  outlier_factor=4.0, min_baseline_windows=3,
                                  suspect_memory_windows=3)
        h = dev.metrics.histogram("read.service_seconds")
        for _ in range(3):                      # warm the EWMA baseline
            for _ in range(10):
                h.observe(1e-3)
            assert mon.sample() is HealthStatus.HEALTHY
        for _ in range(10):                     # a 100x-slower window
            h.observe(1e-1)
        assert mon.sample() is HealthStatus.SUSPECT
        assert mon.latency_outliers == 1
        assert log.snapshot(name="health.latency_outlier")
        # outlier windows are EXCLUDED from the baseline (a sick device
        # must not teach the monitor that sick is normal): a normal window
        # right after is not an outlier, and suspicion decays
        for _ in range(10):
            h.observe(1e-3)
        assert mon.sample() is HealthStatus.SUSPECT   # memory window
        mon.sample()
        assert mon.sample() is HealthStatus.HEALTHY
        assert mon.latency_outliers == 1

    def test_window_errors_mark_suspect_and_smart_log_shape(self):
        dev = _FakeDevice()
        mon = DeviceHealthMonitor(dev, events=EventLog(), name="m0")
        dev.metrics.counter("read_errors").inc()
        dev.metrics.counter("blocks_read").inc(1000)
        assert mon.sample() is HealthStatus.SUSPECT   # 1/1000 < 1% threshold
        smart = mon.smart_log()
        for key in ("status", "read_errors", "media_errors", "zones",
                    "zones_offline", "latency_outliers", "sample_windows"):
            assert key in smart, key
        assert smart["status"] == "SUSPECT"
        assert smart["read_errors"] == 1

    def test_error_rate_past_threshold_degrades(self):
        dev = _FakeDevice()
        mon = DeviceHealthMonitor(dev, events=EventLog(),
                                  error_rate_threshold=0.01)
        dev.metrics.counter("read_errors").inc(5)
        dev.metrics.counter("blocks_read").inc(100)   # 5% >= 1%
        assert mon.sample() is HealthStatus.DEGRADED

    def test_register_on_folds_smart_into_snapshot(self):
        reg = MetricsRegistry("global-ish")
        dev = _FakeDevice()
        mon = DeviceHealthMonitor(dev, events=EventLog(), name="m7")
        mon.register_on(reg)
        snap = reg.snapshot()
        assert snap["health.m7.status_code"] == 0
        assert snap["health.m7.read_errors"] == 0


# ------------------------------------------------------------ alert engine
class TestAlertEngine:
    def _engine(self, rules):
        reg = MetricsRegistry("alerts")
        log = EventLog()
        return AlertEngine(rules, metrics=reg, events=log), reg, log

    def test_error_rate_rule_edge_triggers_and_resolves(self):
        engine, reg, log = self._engine([ErrorRateRule()])
        c = reg.counter("read_errors")
        assert engine.evaluate() == []          # zero baseline: quiet
        c.inc(3)
        fired = engine.evaluate()
        assert [a.rule for a in fired] == ["error_rate"]
        assert engine.evaluate() == []          # still broken, no re-page
        resolved = log.snapshot(name="alert.resolved")
        assert len(resolved) == 1               # growth stopped: cleared
        c.inc()
        assert len(engine.evaluate()) == 1      # a NEW incident re-fires

    def test_tenant_slo_rule_fires_per_breaching_tenant_only(self):
        engine, reg, log = self._engine([TenantLatencySLORule(0.01)])
        reg.histogram("tenant.a.offload_latency_seconds").observe(0.2)
        reg.histogram("tenant.b.offload_latency_seconds").observe(0.001)
        reg.histogram("tenant.idle.offload_latency_seconds")  # no samples
        fired = engine.evaluate()
        assert [a.tags["tenant"] for a in fired] == ["a"]
        assert log.snapshot(name="alert.tenant_p99_slo")
        # empty histograms publish no p99 key, so the idle tenant can
        # never breach (the satellite contract the rule relies on)
        assert "tenant.idle.offload_latency_seconds.p99" not in reg.snapshot()

    def test_health_promotion_rule_drives_sampling_and_callbacks(self):
        log = EventLog()
        dev = ZonedDevice(num_zones=2, zone_bytes=1 << 20, block_bytes=4096)
        mon = DeviceHealthMonitor(dev, events=log, name="m0")
        engine = AlertEngine([HealthPromotionRule(mon)],
                             metrics=MetricsRegistry("x"), events=log)
        reactions: list = []
        engine.on_alert(reactions.append)
        assert engine.evaluate() == []
        dev.set_offline(0)                      # 1/2 >= 0.5: DEGRADED
        fired = engine.evaluate()
        assert [a.rule for a in fired] == ["member_degraded"]
        assert reactions and reactions[0].tags["status"] == "DEGRADED"
        assert mon.status is HealthStatus.DEGRADED   # rule drove sample()
        assert log.snapshot(name="alert.member_degraded")

    def test_broken_rule_does_not_stop_the_sweep(self):
        class Broken(ErrorRateRule):
            def check(self, ctx):
                raise RuntimeError("boom")

        engine, reg, _ = self._engine([Broken(), ErrorRateRule()])
        engine.evaluate()
        reg.counter("x_errors").inc()
        assert [a.rule for a in engine.evaluate()] == ["error_rate"]

    def test_broken_callback_does_not_wedge_evaluation(self):
        """A raising on_alert callback (e.g. a promotion handler hitting an
        exhausted spare pool) must be isolated like a raising rule: the
        other callbacks still run, evaluate() returns normally, and the
        incident still clears with ``alert.resolved`` later."""
        engine, reg, log = self._engine([ErrorRateRule()])
        seen: list = []

        def broken(alert):
            raise RuntimeError("promotion handler crashed")

        engine.on_alert(broken)
        engine.on_alert(seen.append)            # registered AFTER the bomb
        c = reg.counter("read_errors")
        engine.evaluate()                       # baseline
        c.inc(2)
        fired = engine.evaluate()               # must not raise
        assert [a.rule for a in fired] == ["error_rate"]
        assert len(seen) == 1                   # later callback still ran
        errs = log.snapshot(name="alert.callback_error")
        assert errs and errs[0].tags["rule"] == "error_rate"
        assert "broken" in errs[0].message
        # growth stopped: the incident must still resolve on the next sweep
        engine.evaluate()
        assert log.snapshot(name="alert.resolved")
        assert engine.active("error_rate")["error_rate"] == set()


# ------------------------------------------------------- queue event hooks
class TestQueueEvents:
    def test_sq_reject_publishes_event(self):
        from repro.array.queues import (OffloadCommand, QueueFullError,
                                        SubmissionQueue)
        log = event_log()
        seq0 = log.last_seq()
        sq = SubmissionQueue("t0", depth=1)

        def cmd():
            return OffloadCommand(program=None, zone_id=0, block_off=0,
                                  n_blocks=None, tier=None, tenant="t0")

        sq.submit(cmd())
        with pytest.raises(QueueFullError):
            sq.submit(cmd())
        rejects = log.snapshot(name="sq.reject", since_seq=seq0)
        assert rejects and rejects[0].tags["tenant"] == "t0"


# --------------------------------------------- the live-array pipeline
class TestLiveArrayPipeline:
    def test_member_death_walks_counters_events_alerts_and_tenants(self):
        """Zones of one raid1 member die under two tenants' offloads: the
        member's counters move, it is sampled SUSPECT and then DEGRADED,
        the alert fires into the log and the callback, the probe's error
        growth fires the error-rate rule, degraded offloads stay
        bit-identical and are charged to the tenant that ran them, and an
        impossible p99 SLO pages for each tenant. Runs on the global
        registry and event log, the shape the scheduler publishes to."""
        rand_max = 2**31 - 1
        zone_bytes = 256 * 4096
        data = np.random.default_rng(0).integers(
            0, rand_max, zone_bytes // 4, dtype=np.int32)
        expected = int((data > rand_max // 2).sum())
        program = filter_count("int32", "gt", rand_max // 2)
        # 4 zones a member: killing member 1's zones one at a time walks
        # it HEALTHY -> SUSPECT (1/4 offline) -> DEGRADED (3/4 >= 0.5)
        devices = [ZonedDevice(num_zones=4, zone_bytes=zone_bytes,
                               block_bytes=4096) for _ in range(2)]
        array = StripedZoneArray(devices, stripe_blocks=64,
                                 redundancy="raid1")
        array.zone_append(0, data)

        log = event_log()
        seq0 = log.last_seq()
        monitor = ArrayHealthMonitor(array)
        monitor.register_on(registry())
        promoted: list = []            # the spare-promotion callback's inbox
        engine = AlertEngine(rules=[
            HealthPromotionRule(monitor),
            ErrorRateRule(pattern="health.*_errors", name="error_rate"),
        ])
        engine.on_alert(promoted.append)

        with OffloadScheduler(array) as sched:
            sched.register_tenant("alice")
            sched.register_tenant("bob")
            for tenant in ("alice", "bob"):
                for _ in range(2):
                    sched.nvm_cmd_bpf_run(program, 0, tenant=tenant)
                    assert int(sched.nvm_cmd_bpf_result()) == expected
            assert engine.evaluate() == []
            assert monitor.worst() is HealthStatus.HEALTHY
            ts = sched.tenant_stats()
            for tenant in ("alice", "bob"):
                t = ts[tenant]
                assert t["ops"] >= 2 and t["bytes"] > 0, t
                assert t["p99_s"] >= t["p50_s"] > 0.0, t

            # one zone of member 1 dies mid-stream
            before = devices[1].metrics.snapshot()
            array.set_offline(0, device=1)
            after = devices[1].metrics.snapshot()
            assert after["zone_offline_transitions"] == \
                before.get("zone_offline_transitions", 0) + 1

            for _ in range(2):
                stats = sched.nvm_cmd_bpf_run(program, 0, tenant="alice")
                assert int(sched.nvm_cmd_bpf_result()) == expected
            assert stats.degraded_reads > 0
            assert stats.tenant == "alice"
            tot = stats.tenant_totals
            assert tot["degraded_reads"] > 0 and tot["ops"] > 0, tot
            assert tot["bytes"] > 0 and tot["p99_s"] >= tot["p50_s"] > 0.0
            assert sched.tenant_stats()["bob"]["degraded_reads"] == 0

            # SUSPECT: sampled by the promotion rule, below its threshold
            assert engine.evaluate() == []
            assert monitor.statuses()[1] is HealthStatus.SUSPECT
            assert [e for e in log.snapshot(name="health.status",
                                            since_seq=seq0)
                    if e.tags.get("to_status") == "SUSPECT"]

            # a host probe of the dead zone moves the SMART error counter
            with pytest.raises(ZNSError):
                devices[1].read_blocks(0, 0, 1)
            assert devices[1].stats["read_errors"] >= 1

            # past the zone-fraction threshold: DEGRADED, alert, callback
            array.set_offline(1, device=1)
            array.set_offline(2, device=1)
            fired = engine.evaluate()
            assert any(a.rule == "member_degraded" for a in fired), fired
            assert any(a.rule == "member_degraded" for a in promoted)
            assert any(a.rule == "error_rate" for a in fired), fired
            assert monitor.statuses()[1] >= HealthStatus.DEGRADED
            for name in ("alert.member_degraded", "array.member_offline",
                         "zone.offline", "array.degraded_read"):
                assert log.snapshot(name=name, since_seq=seq0), name

            engine.add_rule(TenantLatencySLORule(1e-9))
            slo = [a for a in engine.evaluate() if a.rule == "tenant_p99_slo"]
            assert {a.tags["tenant"] for a in slo} >= {"alice", "bob"}, slo
