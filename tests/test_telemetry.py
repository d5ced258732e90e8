"""Telemetry layer: span tracing + metrics registry.

These tests deliberately avoid ``registry().reset()``: the global registry
carries collectors wired at import time (the compile cache registers its
aggregate collector when ``repro.core.cache`` first loads), and resetting it
would silently unhook them for every later test in the process. Everything
here runs on private ``MetricsRegistry`` instances or on the trace module,
whose ``clear()`` is safe to call per test.
"""
from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from repro.telemetry import trace
from repro.telemetry.metrics import (Counter, Gauge, Histogram,
                                     MetricsRegistry, StatsView,
                                     default_latency_buckets, registry)


@pytest.fixture(autouse=True)
def _fresh_trace():
    trace.set_enabled(False)
    trace.clear()
    yield
    trace.set_enabled(False)
    trace.clear()


# ---------------------------------------------------------------- metrics
class TestCounterGauge:
    def test_counter_inc_and_set(self):
        c = Counter("x")
        c.inc()
        c.inc(41)
        assert c.value == 42
        c.set(0)
        assert c.value == 0

    def test_gauge_set_and_max(self):
        g = Gauge("depth")
        g.set(3.0)
        g.max(2.0)
        assert g.value == 3.0
        g.max(7.5)
        assert g.value == 7.5

    def test_counter_stress_exact_totals(self):
        """8 writer threads, every increment lands: the property the old
        unlocked ``stats[k] += n`` dicts did NOT have."""
        reg = MetricsRegistry("stress")
        c = reg.counter("hits")
        h = reg.histogram("lat")
        n_threads, per_thread = 8, 5000
        start = threading.Barrier(n_threads)

        def work():
            start.wait()
            for i in range(per_thread):
                c.inc()
                h.observe(1e-5 * (1 + i % 7))

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n_threads * per_thread
        assert h.count == n_threads * per_thread


class TestHistogram:
    def test_quantiles_vs_numpy(self):
        """Interpolated p50/p95/p99 must land within one bucket width of
        numpy's exact percentiles for a log-uniform latency sample."""
        rng = np.random.default_rng(7)
        sample = 10 ** rng.uniform(-5.5, -1.5, 20_000)   # 3µs .. 30ms
        h = Histogram("lat")
        for v in sample:
            h.observe(float(v))
        assert h.count == sample.size
        assert h.sum == pytest.approx(sample.sum())
        for q in (50, 95, 99):
            exact = float(np.percentile(sample, q))
            est = h.percentile(q)
            # bucket geometry is ratio-2: the estimate may be off by at most
            # one bucket, i.e. within [exact/2, exact*2]
            assert exact / 2 <= est <= exact * 2, (q, exact, est)

    def test_exact_stats_and_bounds(self):
        h = Histogram("lat", buckets=[1.0, 2.0, 4.0])
        for v in (0.5, 1.5, 3.0, 100.0):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(105.0)
        snap = h.snapshot()
        assert snap["min"] == 0.5
        assert snap["max"] == 100.0
        assert snap["count"] == 4
        # quantiles never exceed the observed max (overflow interpolates
        # toward max, not toward infinity)
        assert h.percentile(99) <= 100.0

    def test_empty(self):
        h = Histogram("lat")
        assert h.percentile(99) == 0.0      # explicit query stays defined
        snap = h.snapshot()
        assert snap["count"] == 0
        # no fabricated quantiles: an idle series must read as "no data",
        # not as p99=0.0 "perfect latency" (which would satisfy any SLO)
        assert not any(k.startswith("p") for k in snap)
        h.observe(1.0)
        snap = h.snapshot()
        assert snap["count"] == 1 and "p99" in snap

    def test_registry_snapshot_omits_empty_histogram_quantiles(self):
        reg = MetricsRegistry("t")
        reg.histogram("idle")
        reg.histogram("busy").observe(0.5)
        snap = reg.snapshot()
        assert "idle.count" in snap and "idle.p99" not in snap
        assert snap["busy.p99"] > 0.0

    def test_default_buckets_cover_emulated_io(self):
        b = default_latency_buckets()
        assert b[0] == pytest.approx(1e-6)
        assert b[-1] > 60.0
        assert list(b) == sorted(b)


class TestRegistry:
    def test_get_or_create_and_type_check(self):
        reg = MetricsRegistry("t")
        assert reg.counter("a") is reg.counter("a")
        with pytest.raises(TypeError):
            reg.gauge("a")

    def test_snapshot_delta(self):
        reg = MetricsRegistry("t")
        c = reg.counter("ops")
        h = reg.histogram("lat")
        g = reg.gauge("ratio")
        c.inc(10)
        h.observe(0.5)
        g.set(0.25)
        before = reg.snapshot()
        c.inc(5)
        h.observe(1.5)
        g.set(0.75)
        d = reg.delta(before)
        assert d["ops"] == 5
        assert d["lat.count"] == 1
        assert d["lat.sum"] == pytest.approx(1.5)
        assert d["ratio"] == 0.75          # gauges stay point-in-time

    def test_collectors_fold_in_and_failures_are_isolated(self):
        reg = MetricsRegistry("t")
        reg.counter("own").inc(1)
        reg.register_collector("ext", lambda: {"size": 3})
        reg.register_collector("dead", lambda: 1 / 0)
        snap = reg.snapshot()
        assert snap["ext.size"] == 3
        assert snap["own"] == 1
        assert not any(k.startswith("dead") for k in snap)

    def test_dump_is_textual(self):
        reg = MetricsRegistry("t")
        reg.counter("ops").inc(3)
        text = reg.dump()
        assert "ops" in text and "3" in text

    def test_global_registry_is_shared(self):
        assert registry() is registry()

    def test_stats_view_dict_semantics(self):
        reg = MetricsRegistry("t")
        a, b = reg.counter("a"), reg.counter("b")
        view = StatsView({"a": a, "b": b})
        a.inc(7)
        assert view["a"] == 7
        assert dict(view) == {"a": 7, "b": 0}
        assert len(view) == 2
        view["a"] = 0                       # the test-suite reset idiom
        assert a.value == 0
        with pytest.raises(TypeError):
            del view["a"]


# ------------------------------------------------------------------ trace
class TestTrace:
    def test_disabled_is_noop(self):
        assert not trace.enabled()
        with trace.span("nothing", tenant="t0"):
            trace.instant("marker")
            trace.event_complete("dev.read", 0.0, 1.0, track="dev0/z0")
        assert trace.drain() == []
        assert trace.dropped() == 0

    def test_span_nesting_inherits_tags(self):
        with trace.tracing(True):
            with trace.span("outer", tenant="t0", zone=3):
                with trace.span("inner", op="read"):
                    pass
        evs = {e["name"]: e for e in trace.drain()}
        assert evs["inner"]["tags"] == {"tenant": "t0", "zone": 3,
                                        "op": "read"}
        assert evs["outer"]["tags"] == {"tenant": "t0", "zone": 3}
        # inner closed first and nests inside outer's window
        assert evs["outer"]["ts"] <= evs["inner"]["ts"]
        assert (evs["inner"]["ts"] + evs["inner"]["dur"]
                <= evs["outer"]["ts"] + evs["outer"]["dur"] + 1e-6)

    def test_spans_across_threads(self):
        """Each thread records into its own ring; nesting context does not
        leak between threads."""
        done = threading.Barrier(5)

        def work(i: int):
            with trace.span(f"thread{i}", idx=i):
                pass
            # hold every thread alive until all have recorded, so thread
            # idents (and therefore ring tids) cannot be reused
            done.wait()

        with trace.tracing(True):
            with trace.span("main", tenant="t0"):
                threads = [threading.Thread(target=work, args=(i,))
                           for i in range(4)]
                for t in threads:
                    t.start()
                done.wait()
                for t in threads:
                    t.join()
        evs = trace.drain()
        names = {e["name"] for e in evs}
        assert names == {"main"} | {f"thread{i}" for i in range(4)}
        by_name = {e["name"]: e for e in evs}
        tids = {e["tid"] for e in evs}
        assert len(tids) == 5               # five distinct rings
        # worker spans started from plain threads have no contextvar parent:
        # no tag leakage from "main"
        for i in range(4):
            assert by_name[f"thread{i}"]["tags"] == {"idx": i}

    def test_event_complete_lands_on_virtual_track(self):
        with trace.tracing(True):
            trace.event_complete("dev.read", 100.0, 0.002, track="dev0/z1",
                                 nblocks=8)
        (ev,) = trace.drain()
        assert ev["track"] == "dev0/z1"
        assert ev["ts"] == 100.0
        assert ev["dur"] == 0.002

    def test_ring_overflow_counts_drops(self):
        with trace.tracing(True):
            for _ in range(trace.RING_CAPACITY + 10):
                trace.instant("x")
        assert trace.dropped() == 10
        assert len(trace.drain()) == trace.RING_CAPACITY

    def test_chrome_export_round_trip(self, tmp_path):
        with trace.tracing(True):
            with trace.span("offload.execute", tenant="t0"):
                trace.instant("marker", note="hi")
            trace.event_complete("dev.read", 50.0, 0.001, track="dev0/z0")
        path = tmp_path / "trace.json"
        n = trace.export_chrome(str(path))
        doc = json.loads(path.read_text())
        evs = doc["traceEvents"]
        assert len(evs) == n
        assert doc["otherData"]["dropped_events"] == 0
        by_ph: dict[str, list] = {}
        for e in evs:
            by_ph.setdefault(e["ph"], []).append(e)
        assert set(by_ph) <= {"X", "M", "i"}
        # complete events carry µs ts/dur; the device event sits on pid 2
        dev = next(e for e in by_ph["X"] if e["name"] == "dev.read")
        assert dev["pid"] == 2
        assert dev["dur"] == pytest.approx(1000.0)   # 0.001 s -> 1000 µs
        host = next(e for e in by_ph["X"] if e["name"] == "offload.execute")
        assert host["pid"] == 1
        assert host["args"]["tenant"] == "t0"
        # metadata names both processes and every row
        meta_names = {m["args"]["name"] for m in by_ph["M"]
                      if m["name"] == "process_name"}
        assert meta_names == {"host threads", "device virtual time"}
        track_rows = {m["args"]["name"] for m in by_ph["M"]
                      if m["name"] == "thread_name" and m["pid"] == 2}
        assert track_rows == {"dev0/z0"}
        # timestamps are rebased: the earliest event starts near zero
        assert min(e["ts"] for e in by_ph["X"]) == pytest.approx(0.0, abs=1.0)

    def test_clear_forgets_everything(self):
        with trace.tracing(True):
            trace.instant("x")
        assert trace.drain()
        trace.clear()
        assert trace.drain() == []


# ------------------------------------------------------- instrumented code
class TestInstrumentation:
    def test_device_stats_view_and_histograms(self):
        from repro.zns import ZonedDevice
        dev = ZonedDevice(num_zones=1, zone_bytes=1 << 20, block_bytes=4096,
                          read_us_per_block=1.0, append_us_per_block=1.0)
        data = np.arange((1 << 18) // 4, dtype=np.int32)
        dev.zone_append(0, data)
        dev.read_blocks(0, 0, 4)
        assert dev.stats["blocks_appended"] > 0
        assert dev.stats["blocks_read"] == 4
        snap = dev.metrics.snapshot()
        assert snap["read.service_seconds.count"] >= 1
        assert snap["append.service_seconds.count"] >= 1
        dev.stats["blocks_read"] = 0        # legacy reset idiom still works
        assert dev.stats["blocks_read"] == 0

    def test_device_virtual_track_events(self):
        from repro.zns import ZonedDevice
        dev = ZonedDevice(num_zones=1, zone_bytes=1 << 20, block_bytes=4096,
                          read_us_per_block=1.0)
        dev.zone_append(0, np.arange(4096 // 4, dtype=np.int32))
        with trace.tracing(True):
            dev.read_blocks(0, 0, 1)
        evs = [e for e in trace.drain() if e["name"] == "dev.read"]
        assert len(evs) == 1
        assert evs[0]["track"] == f"dev{dev.dev_ordinal}/z0"
        assert evs[0]["dur"] == pytest.approx(1e-6, rel=0.5)

    def test_traced_array_offload_exports_host_and_device_rows(self, tmp_path):
        """One traced offload over a two-member array exports a Chrome
        trace with its host span on pid 1 and the members' virtual-time
        reads on pid 2, every event well formed and none dropped."""
        from repro.array import OffloadScheduler, StripedZoneArray
        from repro.core import filter_count
        from repro.zns import ZonedDevice
        data = np.random.default_rng(0).integers(0, 2**31 - 1, (1 << 20) // 4,
                                                 dtype=np.int32)
        devices = [ZonedDevice(num_zones=1, zone_bytes=1 << 20,
                               block_bytes=4096, read_us_per_block=1.0)
                   for _ in range(2)]
        program = filter_count("int32", "gt", 2**30)
        with StripedZoneArray(devices, stripe_blocks=16) as array:
            array.zone_append(0, data)
            with OffloadScheduler(array) as sched:
                sched.nvm_cmd_bpf_run(program, 0)    # compile untraced
                trace.clear()
                with trace.tracing(True):
                    sched.nvm_cmd_bpf_run(program, 0)
                assert int(sched.nvm_cmd_bpf_result()) == \
                    int((data > 2**30).sum())
        path = tmp_path / "trace.json"
        trace.export_chrome(str(path))
        doc = json.loads(path.read_text())
        evs = doc["traceEvents"]
        assert evs and doc["otherData"]["dropped_events"] == 0
        for e in evs:
            assert isinstance(e["name"], str) and e["name"]
            assert e["ph"] in {"X", "M", "i"}
            assert isinstance(e["pid"], int)
            if e["ph"] != "M":
                assert isinstance(e["tid"], int) and e["ts"] >= 0
            if e["ph"] == "X":
                assert e["dur"] >= 0
        spans = {(e["pid"], e["name"]) for e in evs if e["ph"] == "X"}
        assert (1, "offload.execute") in spans
        assert (2, "dev.read") in spans
        assert {m["args"]["name"] for m in evs if m["name"] == "process_name"} \
            == {"host threads", "device virtual time"}

    def test_checkpoint_store_stats_migrated(self):
        from repro.train.checkpoint import ZonedCheckpointStore
        from repro.zns import ZonedDevice
        dev = ZonedDevice(num_zones=4, zone_bytes=1 << 20, block_bytes=4096)
        store = ZonedCheckpointStore(device=dev, keep=2)
        tree = {"w": np.arange(1024, dtype=np.int32)}
        store.save(0, tree)
        assert store.stats["bytes_copied"] >= tree["w"].nbytes
        got = store.restore(like=tree)
        assert np.array_equal(got["w"], tree["w"])
        snap = store.metrics.snapshot()
        assert snap["save_seconds.count"] == 1
        assert snap["restore_seconds.count"] == 1
        assert snap["bytes_viewed"] > 0

    def test_global_registry_sees_compile_cache(self):
        import repro.core.cache  # noqa: F401  (wires the collector)
        snap = registry().snapshot()
        assert "compile_cache.hits" in snap
        assert "compile_cache.live_caches" in snap
