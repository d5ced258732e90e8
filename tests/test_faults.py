"""Deterministic fault injection: taxonomy, retry/timeout datapath, crash
harness.

Contracts pinned here:

  * the injector is a pure function of (seed, key, op, seq) — identical
    schedules across instances and runs, per-class salt independence,
    scriptable ``force`` overrides;
  * injected faults are **error completions through the ring** (never
    submit-time raises), absorbed by the bounded retry policy, escalated
    into the existing health/degraded pipeline only on budget exhaustion;
  * an 8-member raid1 array serving offloads under 1% and 5% injected
    read errors answers bit-identically with nobody ejected;
  * torn appends fence the logical zone at completion time; hung commands
    are rescued by per-op timeouts or diagnosed by ``result(timeout=)``;
  * two runs with one seed produce byte-identical offload results and the
    identical ordered fault/retry event sequence (raid1 and xor);
  * power loss at every append-completion boundary of a striped checkpoint
    save recovers to a committed checkpoint or refuses cleanly.
"""
from __future__ import annotations

import time

import numpy as np
import pytest

from repro.array import ArrayOffloadError, OffloadScheduler, StripedZoneArray
from repro.core import CsdTier, filter_count, filter_sum
from repro.faults import (FaultInjector, FaultSpec, IoTimeoutError,
                          RetryPolicy, TornAppendError, TransientIOError)
from repro.faults.crash import CrashConsistencyError, PowerLossHarness
from repro.telemetry import (AlertEngine, ArrayHealthMonitor, HealthStatus,
                             MetricsRegistry, retry_storm_rule)
from repro.telemetry.events import event_log
from repro.telemetry.health import DeviceHealthMonitor
from repro.zns import ZNSError, ZonedDevice

BLOCK = 4096
RAND_MAX = 2**31 - 1


def _dev(num_zones=2, zone_blocks=64, **kw) -> ZonedDevice:
    return ZonedDevice(num_zones=num_zones, zone_bytes=zone_blocks * BLOCK,
                       block_bytes=BLOCK, **kw)


def _blocks(n, fill=7) -> np.ndarray:
    return np.full(n * BLOCK, fill, dtype=np.uint8)


# ------------------------------------------------------------ the injector
class TestFaultInjector:
    def test_identical_seeds_identical_schedules(self):
        spec = FaultSpec(read_error_rate=0.2, append_error_rate=0.1,
                         latency_spike_rate=0.1, hang_rate=0.05,
                         torn_append_rate=0.1)
        runs = []
        for _ in range(2):
            inj = FaultInjector(42, spec)
            kinds = [(inj.decide(0, "read", 0, 8).kind,
                      inj.decide(1, "append", 1, 8).kind)
                     for _ in range(200)]
            runs.append((kinds, inj.schedule_log()))
        assert runs[0] == runs[1]
        # and the schedule is non-trivial at these rates
        assert any(k[0] or k[1] for k in runs[0][0])

    def test_different_seeds_diverge(self):
        spec = FaultSpec(read_error_rate=0.3)
        a = FaultInjector(1, spec)
        b = FaultInjector(2, spec)
        ka = [a.decide(0, "read", 0, 1).kind for _ in range(200)]
        kb = [b.decide(0, "read", 0, 1).kind for _ in range(200)]
        assert ka != kb

    def test_per_class_salt_independence(self):
        """Raising the media rate must not move WHICH submissions hang."""
        hangs = []
        for media_rate in (0.0, 0.5):
            inj = FaultInjector(9, FaultSpec(read_error_rate=media_rate,
                                             hang_rate=0.1))
            hangs.append([i for i in range(300)
                          if inj.decide(0, "read", 0, 1).kind == "hang"])
        assert hangs[0] == hangs[1] and hangs[0]

    def test_keys_draw_independent_streams(self):
        inj = FaultInjector(3, FaultSpec(read_error_rate=0.3))
        k0 = [inj.decide(0, "read", 0, 1).kind for _ in range(100)]
        k1 = [inj.decide(1, "read", 0, 1).kind for _ in range(100)]
        assert k0 != k1

    def test_force_overrides_the_draw(self):
        inj = FaultInjector(0)         # zero rates: never fires on its own
        inj.force(0, "read", 2, "media")
        kinds = [inj.decide(0, "read", 0, 1).kind for _ in range(4)]
        assert kinds == [None, None, "media", None]
        assert inj.injected["media"] == 1

    def test_torn_degrades_outside_fresh_multiblock_appends(self):
        inj = FaultInjector(0)
        for seq, (op, nblocks, retry) in enumerate(
                [("read", 8, False), ("append", 1, False),
                 ("append", 8, True)]):
            inj.force(0, op, seq if op == "read" else seq - 1, "torn")
        assert inj.decide(0, "read", 0, 8).kind == "media"
        assert inj.decide(0, "append", 0, 1).kind == "media"
        assert inj.decide(0, "append", 0, 8, retry=True).kind == "media"

    def test_per_key_spec_and_jitter(self):
        sick = FaultSpec(read_error_rate=1.0)
        inj = FaultInjector(5, per_key={3: sick})
        assert inj.spec_for(3) is sick
        assert inj.decide(3, "read", 0, 1).kind == "media"
        assert inj.decide(0, "read", 0, 1).kind is None
        js = [inj.jitter01(0, "read") for _ in range(50)]
        assert all(0.0 <= j < 1.0 for j in js)
        inj2 = FaultInjector(5)
        assert js == [inj2.jitter01(0, "read") for _ in range(50)]


# ------------------------------------------------------------ the taxonomy
class TestTaxonomy:
    def test_retryable_bits_and_zns_separation(self):
        assert TransientIOError("x").retryable
        assert IoTimeoutError("x").retryable
        assert not TornAppendError("x").retryable
        assert issubclass(TornAppendError, TransientIOError)
        assert issubclass(IoTimeoutError, TransientIOError)
        assert not issubclass(TransientIOError, ZNSError)

    def test_error_carries_diagnostics(self):
        e = TransientIOError("boom", op="read", device="dev7", zone_id=3,
                             attempt=2)
        assert (e.op, e.device, e.zone_id, e.attempt) == ("read", "dev7", 3, 2)


# --------------------------------------------------- device datapath faults
class TestDeviceDatapath:
    def test_error_is_a_completion_not_a_raise(self):
        d = _dev()
        d.zone_append(0, _blocks(4))
        inj = FaultInjector(0)
        inj.attach(d, key=0)           # no policy: single attempt
        inj.force(0, "read", 0, "media")
        fut = d.submit_read(0, 0, 4)   # must NOT raise at submit time
        with pytest.raises(TransientIOError):
            fut.result()
        assert isinstance(fut.error, TransientIOError)
        assert d.stats["read_errors"] == 1      # budget of 1 exhausted

    def test_retry_absorbs_transient_media_error(self):
        d = _dev()
        data = _blocks(4, fill=9)
        d.zone_append(0, data)
        inj = FaultInjector(0)
        inj.attach(d, key=0, policy=RetryPolicy(max_attempts=3,
                                                backoff_base_s=0.0))
        inj.force(0, "read", 0, "media")
        got = np.asarray(d.submit_read(0, 0, 4).result()).reshape(-1)
        assert np.array_equal(got, data)
        s = d.stats
        assert s["retries"] == 1 and s["faults_injected"] == 1
        assert s["read_errors"] == 0, "absorbed fault must stay soft"

    def test_exhausted_budget_escalates_once(self):
        d = _dev()
        d.zone_append(0, _blocks(2))
        inj = FaultInjector(0, FaultSpec(read_error_rate=1.0))
        inj.attach(d, key=0, policy=RetryPolicy(max_attempts=3,
                                                backoff_base_s=0.0))
        seq0 = event_log().last_seq()
        with pytest.raises(TransientIOError):
            d.read_blocks(0, 0, 2)     # sync path rides the same machinery
        s = d.stats
        assert s["retries"] == 2       # attempts 2 and 3
        assert s["read_errors"] == 1   # ONE escalation, not one per attempt
        names = [e.name for e in event_log().snapshot(since_seq=seq0)]
        assert names.count("io.retry") == 2
        assert names.count("io.retry_exhausted") == 1

    def test_latency_spike_injects_delay_not_error(self):
        d = _dev()
        d.zone_append(0, _blocks(2))
        inj = FaultInjector(0)
        inj.attach(d, key=0)
        inj.force(0, "read", 0, None, extra_latency_s=0.01)
        t0 = time.perf_counter()
        fut = d.submit_read(0, 0, 2)
        assert np.asarray(fut.result()).size == 2 * BLOCK
        # the spike occupies the zone's virtual-time die for 10ms
        assert time.perf_counter() - t0 >= 0.009
        assert d.stats["faults_injected"] == 1
        assert d.stats["read_errors"] == 0

    def test_hang_rescued_by_policy_timeout(self):
        d = _dev()
        d.zone_append(0, _blocks(2))
        inj = FaultInjector(0)
        inj.attach(d, key=0, policy=RetryPolicy(max_attempts=1,
                                                timeout_s=0.01))
        inj.force(0, "read", 0, "hang")
        with pytest.raises(IoTimeoutError):
            d.submit_read(0, 0, 2).result(timeout=5.0)
        assert d.stats["io_timeouts"] == 1

    def test_hang_then_timeout_then_retry_succeeds(self):
        d = _dev()
        data = _blocks(3, fill=5)
        d.zone_append(0, data)
        inj = FaultInjector(0)
        inj.attach(d, key=0, policy=RetryPolicy(max_attempts=2,
                                                backoff_base_s=0.0,
                                                timeout_s=0.01))
        inj.force(0, "read", 0, "hang")
        got = np.asarray(d.submit_read(0, 0, 3).result(timeout=5.0))
        assert np.array_equal(got.reshape(-1), data)
        # the timed-out attempt lands in io_timeouts (retries counts only
        # error-completion resubmissions), and nothing escalated hard
        assert d.stats["io_timeouts"] == 1
        assert d.stats["read_errors"] == 0

    def test_stuck_op_diagnostic_names_the_op(self):
        d = _dev()
        d.zone_append(0, _blocks(2))
        inj = FaultInjector(0)
        inj.attach(d, key=0)           # no timeout: genuinely stuck
        inj.force(0, "read", 0, "hang")
        fut = d.submit_read(0, 0, 2)
        with pytest.raises(TimeoutError) as ei:
            fut.result(timeout=0.02)
        msg = str(ei.value)
        assert "read" in msg and "zone 0" in msg and "dev" in msg

    def test_torn_append_lands_prefix_and_fails_hard(self):
        d = _dev()
        inj = FaultInjector(0)
        inj.attach(d, key=0, policy=RetryPolicy(max_attempts=4,
                                                backoff_base_s=0.0))
        inj.force(0, "append", 0, "torn", torn_keep=0.5)
        fut = d.submit_append(0, _blocks(4))
        with pytest.raises(TornAppendError):
            fut.result()
        assert d.zone(0).write_pointer == 2     # the prefix landed
        s = d.stats
        assert s["append_errors"] == 1          # non-retryable: no retries
        assert s["retries"] == 0

    def test_hung_append_lands_payload_without_completion(self):
        d = _dev()
        inj = FaultInjector(0)
        inj.attach(d, key=0)
        inj.force(0, "append", 0, "hang")
        fut = d.submit_append(0, _blocks(2))
        assert d.zone(0).write_pointer == 2     # durable on the media
        assert not fut.done()                   # the CQE never arrived

    def test_append_retry_replays_same_landing_block(self):
        d = _dev()
        inj = FaultInjector(0)
        inj.attach(d, key=0, policy=RetryPolicy(max_attempts=3,
                                                backoff_base_s=0.0))
        d.zone_append(0, _blocks(1))            # wp=1 before the fault
        inj.force(0, "append", 1, "media")      # seq 1: the next append
        landed = d.submit_append(0, _blocks(2)).result()
        assert landed == 1                      # data effect happened ONCE
        assert d.zone(0).write_pointer == 3
        assert d.stats["retries"] == 1


# ----------------------------------------------------------- array datapath
def _filled_array(n_dev=4, redundancy="raid1", zone_blocks=256,
                  num_zones=2, seed=0, **dev_kw):
    devices = [_dev(num_zones=num_zones, zone_blocks=zone_blocks, **dev_kw)
               for _ in range(n_dev)]
    array = StripedZoneArray(devices, stripe_blocks=16,
                             redundancy=redundancy)
    rng = np.random.default_rng(seed)
    data = rng.integers(0, RAND_MAX, array.zone_blocks * BLOCK // 8,
                        dtype=np.int32)        # half the logical zone
    array.zone_append(0, data)
    return array, data


class TestArrayDatapath:
    def test_fanout_retries_keep_bits_identical(self):
        array, _ = _filled_array()
        baseline = array.read_zone(0).copy()
        inj = FaultInjector(11, FaultSpec(read_error_rate=0.2))
        inj.attach_array(array, policy=RetryPolicy(max_attempts=6,
                                                   backoff_base_s=0.0))
        for _ in range(3):
            assert np.array_equal(array.read_zone(0), baseline)
        assert sum(d.stats["retries"] for d in array.devices) > 0
        assert sum(d.stats["read_errors"] for d in array.devices) == 0

    def test_torn_member_append_fences_the_logical_zone(self):
        array, _ = _filled_array()
        inj = FaultInjector(0)
        inj.attach_array(array)
        inj.force(0, "append", 0, "torn")       # member 0's next append
        seq0 = event_log().last_seq()
        wp0 = array.zone(0).write_pointer
        committed = array.read_blocks(0, 0, wp0).copy()
        with pytest.raises(TornAppendError):
            array.zone_append(0, np.ones(array.stripe_blocks * 2 * BLOCK,
                                         np.uint8))
        assert array.zone(0).state.value == "read_only"
        assert event_log().snapshot(name="array.zone_fenced", since_seq=seq0)
        # pre-tear data still readable bit-identically; the torn extent and
        # fresh appends are refused cleanly, never served as garbage
        assert np.array_equal(array.read_blocks(0, 0, wp0), committed)
        with pytest.raises(ZNSError):
            array.read_zone(0)         # tail reaches the un-landed member blocks
        with pytest.raises(ZNSError) as ei:
            array.zone_append(0, _blocks(1))
        assert "fenced" in str(ei.value)
        # reset clears the fence (the documented recovery path)
        array.reset_zone(0)
        array.zone_append(0, _blocks(1))

    def test_fanout_join_timeout_names_stuck_member(self):
        array, _ = _filled_array()
        inj = FaultInjector(0)
        inj.attach_array(array)
        inj.force(0, "read", 0, "hang")         # member 0 hangs its chunk
        fut = array.submit_read(0, 0, array.stripe_blocks * 2)
        with pytest.raises(TimeoutError) as ei:
            fut.result(timeout=0.02)
        msg = str(ei.value)
        assert "array" in msg and "waiting on" in msg and "read" in msg

    def test_array_sync_reads_accept_timeout_kwarg(self):
        array, _ = _filled_array()
        inj = FaultInjector(0)
        inj.attach_array(array)
        inj.force(0, "read", 0, "hang")
        with pytest.raises(TimeoutError):
            array.read_blocks(0, 0, array.stripe_blocks, timeout=0.02)
        # healthy ops with a timeout budget just work
        assert array.read_blocks(0, 0, array.stripe_blocks,
                                 timeout=5.0).size


# ------------------------------------------------- scheduler + health chain
class TestOffloadUnderFaults:
    def test_offload_bit_identical_under_transients(self):
        array, data = _filled_array()
        expected = int((data > RAND_MAX // 2).sum())
        inj = FaultInjector(21, FaultSpec(read_error_rate=0.15))
        inj.attach_array(array, policy=RetryPolicy(max_attempts=6,
                                                   backoff_base_s=0.0))
        program = filter_count("int32", "gt", RAND_MAX // 2)
        with OffloadScheduler(array) as sched:
            sched.register_tenant("t")
            for _ in range(4):
                sched.nvm_cmd_bpf_run(program, 0, tenant="t")
                assert int(sched.nvm_cmd_bpf_result()) == expected
        assert sum(d.stats["retries"] for d in array.devices) > 0

    def test_exhausted_member_escalates_to_degraded_read(self):
        """A member whose budget exhausts is treated exactly like a dead
        member: the raid1 offload reconstructs from the mirror and still
        returns the healthy answer — the escalation path into the existing
        degraded pipeline."""
        array, data = _filled_array()
        # one full stripe group -> a single 16-block chunk per data member:
        # the batched path is skipped, so the exhaustion surfaces in the
        # per-chunk loop and must fall back to degraded reconstruction
        n_blocks = 2 * array.stripe_blocks
        sub = data[:n_blocks * array.block_bytes // 4]
        expected = int((sub > RAND_MAX // 2).sum())
        inj = FaultInjector(0)
        inj.attach_array(array, policy=RetryPolicy(max_attempts=2,
                                                   backoff_base_s=0.0))
        # member 0's chunk read fails on BOTH budgeted attempts
        inj.force(0, "read", 0, "media")
        inj.force(0, "read", 1, "media")
        seq0 = event_log().last_seq()
        program = filter_count("int32", "gt", RAND_MAX // 2)
        with OffloadScheduler(array) as sched:
            sched.register_tenant("t")
            st = sched.nvm_cmd_bpf_run(program, 0, n_blocks=n_blocks,
                                       tenant="t")
            assert int(sched.nvm_cmd_bpf_result()) == expected
        assert st.degraded_reads == 1
        assert array.devices[0].stats["read_errors"] == 1
        assert event_log().snapshot(name="io.retry_exhausted",
                                    since_seq=seq0)

    @pytest.mark.parametrize("mode,n", [("raid1", 4), ("xor", 3)])
    @pytest.mark.parametrize("tier", [CsdTier.JIT, CsdTier.KERNEL])
    def test_transient_mid_batch_reserves_member_bit_identical(
            self, mode, n, tier):
        """ISSUE 10 fault seam: a single member's read dying INSIDE the
        array-wide batched dispatch must not poison the batch — the
        surviving members' staged chunks still dispatch together, the dead
        member's chunks re-serve individually through degraded
        reconstruction (raid1 mirror / xor parity), and the answer stays
        bit-identical to the fault-free run at both compiled tiers."""
        array, data = _filled_array(n_dev=n, redundancy=mode)
        programs = (filter_count("int32", "gt", RAND_MAX // 2),
                    filter_sum("int32", "lt", RAND_MAX // 4))
        with OffloadScheduler(array) as sched:
            clean = [sched.run_and_fetch(p, 0, tier=tier)[0]
                     for p in programs]
            inj = FaultInjector(0)
            inj.attach_array(array, policy=RetryPolicy(max_attempts=2,
                                                       backoff_base_s=0.0))
            for p, want in zip(programs, clean):
                # member 0's next batched group read fails on BOTH budgeted
                # attempts -> exhaustion surfaces mid-batch
                seq = inj._seq.get((0, "read"), 0)
                inj.force(0, "read", seq, "media")
                inj.force(0, "read", seq + 1, "media")
                got, st = sched.run_and_fetch(p, 0, tier=tier)
                assert np.array_equal(np.asarray(want), np.asarray(got))
                assert st.degraded_reads > 0
                assert st.batched_chunks > 0   # survivors still batched
        assert array.devices[0].stats["read_errors"] > 0

    @pytest.mark.parametrize("tier", [CsdTier.JIT, CsdTier.KERNEL])
    def test_raid0_transients_retry_inside_batch_bit_identical(self, tier):
        """raid0 has no redundancy to re-serve from, so the same seam leans
        on the retry policy alone: transient faults inside the batched
        reads are absorbed below the scheduler and the answer is
        bit-identical; only an EXHAUSTED budget escalates to the clean
        aggregate failure."""
        array, data = _filled_array(n_dev=4, redundancy="raid0")
        expected = int((data > RAND_MAX // 2).sum())
        program = filter_count("int32", "gt", RAND_MAX // 2)
        inj = FaultInjector(33, FaultSpec(read_error_rate=0.2))
        inj.attach_array(array, policy=RetryPolicy(max_attempts=6,
                                                   backoff_base_s=0.0))
        with OffloadScheduler(array) as sched:
            for _ in range(3):
                st = sched.nvm_cmd_bpf_run(program, 0)
                assert int(sched.nvm_cmd_bpf_result()) == expected
                assert st.degraded_reads == 0
            assert sum(d.stats["retries"] for d in array.devices) > 0
            # now exhaust member 0's budget mid-batch: no mirror, no parity
            # -> the offload fails as an aggregate, loudly
            seq = inj._seq.get((0, "read"), 0)
            for k in range(6):
                inj.force(0, "read", seq + k, "media")
            with pytest.raises(ArrayOffloadError, match="degraded"):
                sched.run_and_fetch(program, 0)

    def test_soft_counters_classify_suspect_not_degraded(self):
        d = _dev()
        d.zone_append(0, _blocks(4))
        inj = FaultInjector(0)
        inj.attach(d, key=0, policy=RetryPolicy(max_attempts=4,
                                                backoff_base_s=0.0))
        mon = DeviceHealthMonitor(d)
        assert mon.sample() == HealthStatus.HEALTHY
        inj.force(0, "read", 0, "media")
        d.read_blocks(0, 0, 4)
        assert mon.sample() == HealthStatus.SUSPECT
        smart = mon.smart_log()
        assert smart["retries"] == 1
        assert smart["io_timeouts"] == 0 and smart["faults_injected"] == 1
        # soft counters carry no SUSPECT memory: a quiet window recovers
        assert mon.sample() == HealthStatus.HEALTHY

    def test_retry_storm_rule_fires_and_resolves(self):
        array, _ = _filled_array(n_dev=2, zone_blocks=128)
        inj = FaultInjector(0, FaultSpec(read_error_rate=0.5))
        inj.attach_array(array, policy=RetryPolicy(max_attempts=8,
                                                   backoff_base_s=0.0))
        reg = MetricsRegistry("test_faults_storm")
        monitor = ArrayHealthMonitor(array)
        monitor.register_on(reg)
        engine = AlertEngine(rules=[retry_storm_rule()], metrics=reg)
        assert engine.evaluate() == []
        for _ in range(10):
            array.read_blocks(0, 0, array.stripe_blocks)
        for m in monitor.members:
            m.sample()
        fired = engine.evaluate()
        assert any(a.rule == "retry_storm" for a in fired), fired
        # quiet window: the edge-triggered alert resolves
        for m in monitor.members:
            m.sample()
        engine.evaluate()
        assert not any(k for k in engine.active().get("retry_storm",
                                                      set()))

    @pytest.mark.parametrize("rate", [0.01, 0.05])
    def test_live_offloads_ride_through_injected_read_errors(self, rate):
        """An 8-member raid1 array serving offloads under seeded transient
        read errors: every answer and every zone's bytes equal the
        fault-free truth, no retry budget exhausts, no member leaves the
        HEALTHY/SUSPECT band and no zone goes offline."""
        devices = [_dev(num_zones=2, zone_blocks=128) for _ in range(8)]
        array = StripedZoneArray(devices, stripe_blocks=16,
                                 redundancy="raid1")
        rng = np.random.default_rng(0)
        expected, baseline = [], []
        for z in range(2):
            data = rng.integers(0, RAND_MAX, array.zone_blocks * BLOCK // 8,
                                dtype=np.int32)   # half of each logical zone
            array.zone_append(z, data)
            expected.append(int((data > RAND_MAX // 2).sum()))
            baseline.append(array.read_zone(z).copy())
        # the fills ran clean; only the offloads and read-backs see faults
        inj = FaultInjector(2112, FaultSpec(read_error_rate=rate))
        inj.attach_array(array, policy=RetryPolicy(max_attempts=6,
                                                   backoff_base_s=50e-6))
        program = filter_count("int32", "gt", RAND_MAX // 2)
        with OffloadScheduler(array) as sched:
            sched.register_tenant("t")
            for _ in range(3):
                for z in range(2):
                    sched.nvm_cmd_bpf_run(program, z, tenant="t")
                    assert int(sched.nvm_cmd_bpf_result()) == expected[z]
        for z in range(2):
            assert np.array_equal(array.read_zone(z), baseline[z])
        stats = [d.stats for d in array.devices]
        assert sum(s["read_errors"] + s["append_errors"] for s in stats) == 0
        worst = max(m.sample() for m in ArrayHealthMonitor(array).members)
        assert worst <= HealthStatus.SUSPECT, worst.name
        assert all(array.zone(z).state.value != "offline" for z in range(2))
        if rate >= 0.05:
            assert sum(s["faults_injected"] for s in stats) > 0
            assert sum(s["retries"] for s in stats) > 0


# ------------------------------------------------------ determinism witness
def _deterministic_offload_run(redundancy: str, n_dev: int):
    """One seeded faulty offload run; returns (result bytes, io-event
    sequence keyed by stable member tags, injector transcript)."""
    devices = [_dev(num_zones=2, zone_blocks=128) for _ in range(n_dev)]
    array = StripedZoneArray(devices, stripe_blocks=16,
                             redundancy=redundancy)
    rng = np.random.default_rng(7)
    data = rng.integers(0, RAND_MAX, array.zone_blocks * BLOCK // 8,
                        dtype=np.int32)
    array.zone_append(0, data)
    inj = FaultInjector(1234, FaultSpec(read_error_rate=0.15,
                                        latency_spike_rate=0.1,
                                        latency_spike_s=0.0))
    inj.attach_array(array, policy=RetryPolicy(max_attempts=6,
                                               backoff_base_s=0.0))
    program = filter_count("int32", "gt", RAND_MAX // 2)
    seq0 = event_log().last_seq()
    results = []
    with OffloadScheduler(array, max_workers=1) as sched:
        sched.register_tenant("t")
        for _ in range(4):
            sched.nvm_cmd_bpf_run(program, 0, tenant="t")
            results.append(int(sched.nvm_cmd_bpf_result()))
    raw = array.read_zone(0).tobytes()
    events = [(e.name, e.tags["member"], e.tags["zone"], e.tags["op"],
               e.tags.get("attempt"))
              for e in event_log().snapshot(since_seq=seq0)
              if e.name.startswith("io.")]
    return results, raw, events, inj.schedule_log()


class TestDeterminism:
    @pytest.mark.parametrize("redundancy,n_dev", [("raid1", 8), ("xor", 8)])
    def test_same_seed_same_results_and_fault_sequence(self, redundancy,
                                                       n_dev):
        a = _deterministic_offload_run(redundancy, n_dev)
        b = _deterministic_offload_run(redundancy, n_dev)
        assert a[0] == b[0], "offload results diverged across runs"
        assert a[1] == b[1], "zone bytes diverged across runs"
        assert a[2] == b[2], "fault/retry event sequence diverged"
        assert a[3] == b[3], "injector transcript diverged"
        assert a[2], "schedule injected nothing — determinism untested"


# --------------------------------------------------------- crash consistency
class TestCrashHarness:
    def test_raid1_sweep_never_torn(self, tmp_path):
        h = PowerLossHarness(tmp_path, num_devices=4, num_zones=6,
                             member_zone_bytes=256 * 1024, stripe_blocks=4,
                             redundancy="raid1")
        trees = [(s, {"w": np.arange(300, dtype=np.float32) + s,
                      "b": np.full((17,), s, np.int32)}) for s in (1, 2)]
        outcomes = h.run(trees)
        assert len(outcomes) == len(h.journal) + 1
        assert all(o.ok for o in outcomes)
        # boundary 0 = power loss before anything landed: clean refusal
        assert outcomes[0].refused and outcomes[0].recovered_step is None
        # final boundary = nothing lost: the newest step restores
        assert outcomes[-1].recovered_step == 2
        # monotone recovery: later cuts never restore older steps
        steps = [o.recovered_step for o in outcomes
                 if o.recovered_step is not None]
        assert steps == sorted(steps)

    def test_xor_sweep_and_stride(self, tmp_path):
        h = PowerLossHarness(tmp_path, num_devices=3, num_zones=6,
                             member_zone_bytes=256 * 1024, stripe_blocks=4,
                             redundancy="xor", stride=2)
        outcomes = h.run([(5, {"w": np.arange(200, dtype=np.float32)})])
        assert all(o.ok for o in outcomes)
        assert outcomes[-1].recovered_step == 5
        assert h.summary()["all_ok"]

    def test_violation_raises_with_boundary(self, tmp_path):
        """A harness whose journal LIES (claims a manifest completed that
        never landed) must fail the sweep — the detector detects."""
        h = PowerLossHarness(tmp_path, num_devices=4, num_zones=6,
                             member_zone_bytes=256 * 1024, stripe_blocks=4,
                             redundancy="raid1")
        trees = [(1, {"w": np.arange(64, dtype=np.float32)})]
        h._record_saves(trees)
        # claim step 1 was fully durable after its FIRST member append —
        # recovery at that cut must refuse (no manifest on disk), which now
        # violates the forged lo bound and trips the detector
        step, _end = h._step_end[0]
        h._step_end[0] = (step, 1)
        with pytest.raises(CrashConsistencyError):
            for k in h._boundaries():
                out = h._check_boundary(k, dict(trees), trees[0][1])
                if not out.ok:
                    raise CrashConsistencyError(out.detail)

    def test_checkpoint_store_rides_the_retry_datapath(self, tmp_path):
        """ZonedCheckpointStore.striped(fault_injector=...) saves/restores
        bit-identically under injected read faults, with retries absorbed
        by the member devices."""
        from repro.train.checkpoint import ZonedCheckpointStore
        inj = FaultInjector(77, FaultSpec(read_error_rate=0.1))
        store = ZonedCheckpointStore.striped(
            tmp_path / "ckpt", num_devices=4, num_zones=6,
            member_zone_bytes=256 * 1024, stripe_blocks=4,
            redundancy="raid1", fault_injector=inj,
            retry_policy=RetryPolicy(max_attempts=6, backoff_base_s=0.0))
        tree = {"w": np.arange(2000, dtype=np.float32),
                "b": np.arange(100, dtype=np.int32)}
        store.save(3, tree)
        got = store.restore(like=tree)
        assert np.array_equal(got["w"], tree["w"])
        assert np.array_equal(got["b"], tree["b"])
        assert sum(d.stats["retries"]
                   for d in store.device.devices) > 0
        assert sum(d.stats["read_errors"]
                   for d in store.device.devices) == 0
