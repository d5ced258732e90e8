"""Self-healing array: rebuild-to-spare protocol, ArrayManager loops, scrub.

Pins the ISSUE 8 contracts: member_shard address math against real device
contents, per-zone cutover (rebuilt zones take appends while later zones
still copy), full-lifecycle bit-identity across raid1/xor at 2/4/8 members
(including a member killed mid-rebuild), idempotent alert-path promotion
with incident resolution, unattended recovery with offloads served
bit-identically while the rebuild is held between zones, xor
double-fault degrading OFFLINE without corruption or hangs, scrub
catching injected bit rot and feeding the health monitor, and
checkpoint restore riding a mid-rebuild array.
"""
import threading

import numpy as np
import pytest

from repro.array import ArrayManager, OffloadScheduler, StripedZoneArray
from repro.core.programs import filter_sum
from repro.telemetry.alerts import AlertEngine, HealthPromotionRule
from repro.telemetry.events import event_log
from repro.telemetry.health import ArrayHealthMonitor, HealthStatus
from repro.train.checkpoint import ZonedCheckpointStore
from repro.zns import ZNSError, ZonedDevice, ZoneState, ZoneStateError

BLOCK = 4096
STRIPE = 4


def make_device(num_zones=4, zone_kib=256):
    return ZonedDevice(num_zones=num_zones, zone_bytes=zone_kib * 1024,
                       block_bytes=BLOCK)


def make_array(n_devices, *, num_zones=4, zone_kib=256, stripe=STRIPE,
               redundancy="raid0"):
    devs = [make_device(num_zones, zone_kib) for _ in range(n_devices)]
    return StripedZoneArray(devs, stripe_blocks=stripe, redundancy=redundancy)


def int32_blocks(n_blocks, seed=0, lo=-1000, hi=1000):
    rng = np.random.default_rng(seed)
    return rng.integers(lo, hi, n_blocks * BLOCK // 4, dtype=np.int32)


def kill_member(arr, member, zones=None):
    for z in (range(arr.num_zones) if zones is None else zones):
        arr.set_offline(z, device=member)


def corrupt_block(dev, zone_id, block, offset=17):
    """Flip one byte of a landed block directly in the device's backing
    buffer — silent bit rot no read error will ever report."""
    z = dev.zone(zone_id)
    dev._buf[(z.start_lba + block) * dev.block_bytes + offset] ^= 0xFF


# ------------------------------------------------------- member_shard math
class TestMemberShard:
    @pytest.mark.parametrize("redundancy,n", [
        ("raid0", 3), ("raid1", 2), ("raid1", 4), ("xor", 4), ("xor", 5)])
    @pytest.mark.parametrize("fill", ["row", "rows_partial", "full"])
    def test_shard_matches_device_contents(self, redundancy, n, fill):
        """member_shard over the logical stream must reproduce, byte for
        byte, what each member actually stored — data chunks, mirror
        copies, and rotated parity (tail parity excluded: it never
        landed)."""
        arr = make_array(n, num_zones=2, zone_kib=128, redundancy=redundancy)
        row = STRIPE * arr.data_columns
        wp = {"row": row, "rows_partial": 3 * row + STRIPE + 2,
              "full": arr.zone_blocks}[fill]
        arr.zone_append(0, int32_blocks(wp, seed=wp))
        logical = arr.read_zone(0).reshape(-1, BLOCK)
        wps = arr._member_write_pointers(wp)
        for m, dev in enumerate(arr.devices):
            assert dev.zone(0).write_pointer == wps[m]
            shard = arr.member_shard(m, logical)
            stored = dev.read_blocks(0, 0, wps[m]).reshape(-1, BLOCK)
            assert shard.shape == stored.shape, (m, shard.shape, stored.shape)
            assert np.array_equal(shard, stored), f"member {m} shard differs"

    def test_batched_shards_concatenate(self):
        """Row-aligned batches with the right base_block must concatenate
        to the whole-zone shard — the invariant the rebuild copy loop
        relies on."""
        arr = make_array(4, num_zones=2, zone_kib=128, redundancy="xor")
        row = STRIPE * arr.data_columns
        wp = 5 * row + 3
        arr.zone_append(0, int32_blocks(wp, seed=9))
        logical = arr.read_zone(0).reshape(-1, BLOCK)
        for m in range(arr.n_devices):
            whole = arr.member_shard(m, logical)
            parts = [arr.member_shard(m, logical[b: b + 2 * row],
                                      base_block=b)
                     for b in range(0, wp, 2 * row)]
            assert np.array_equal(np.concatenate(parts), whole)

    def test_unaligned_base_block_rejected(self):
        arr = make_array(4, redundancy="xor")
        with pytest.raises(ValueError, match="aligned"):
            arr.member_shard(0, np.zeros((4, BLOCK), np.uint8), base_block=2)


# ----------------------------------------------- append-refusal diagnostics
class TestRefusalDetail:
    def test_degraded_refusal_names_members_and_mode(self):
        arr = make_array(4, redundancy="xor")
        arr.zone_append(0, int32_blocks(STRIPE * arr.data_columns))
        kill_member(arr, 2, zones=[0])
        with pytest.raises(ZoneStateError) as ei:
            arr.zone_append(0, int32_blocks(1))
        msg = str(ei.value)
        assert "not writable" in msg
        assert "offline members=[2]" in msg
        assert "redundancy=xor" in msg
        assert "array.member_offline" in msg

    def test_rebuilding_refusal_names_the_rebuild(self):
        arr = make_array(2, redundancy="raid1")
        arr.zone_append(0, int32_blocks(STRIPE))
        kill_member(arr, 1)
        arr.replace_member(1, make_device())
        with pytest.raises(ZoneStateError) as ei:
            arr.zone_append(0, int32_blocks(1))
        assert "member 1 rebuilding onto spare" in str(ei.value)


# ------------------------------------------------------- rebuild protocol
class TestRebuildProtocol:
    def test_per_zone_cutover_under_manual_protocol(self):
        """Committing zone 0 makes it writable again while zone 1 is still
        marked — the online property, pinned without thread timing."""
        arr = make_array(4, redundancy="xor")
        row = STRIPE * arr.data_columns
        for z in (0, 1):
            arr.zone_append(z, int32_blocks(2 * row + 3, seed=z))
        logical0 = arr.read_zone(0).reshape(-1, BLOCK)
        kill_member(arr, 1, zones=[0, 1])
        pending = arr.replace_member(1, make_device())
        assert pending == [0, 1]
        member, wp = arr.begin_member_rebuild(0)
        assert (member, wp) == (1, 2 * row + 3)
        shard = arr.member_shard(1, logical0)
        arr.devices[1].submit_append(0, shard).result()
        arr.commit_member_rebuild(0)
        assert arr.zone(0).is_writable
        assert arr.zone(1).state is ZoneState.READ_ONLY
        arr.zone_append(0, int32_blocks(2, seed=77))     # appends resume
        assert arr.rebuilding_zones() == {1: 1}

    def test_commit_refuses_short_copy(self):
        arr = make_array(2, redundancy="raid1")
        arr.zone_append(0, int32_blocks(3 * STRIPE))
        kill_member(arr, 0)
        arr.replace_member(0, make_device())
        arr.begin_member_rebuild(0)
        arr.devices[0].submit_append(0, np.zeros(STRIPE * BLOCK,
                                                 np.uint8)).result()
        with pytest.raises(ZoneStateError, match="cutover.*refused"):
            arr.commit_member_rebuild(0)

    def test_begin_restarts_partial_copy_from_zero(self):
        arr = make_array(2, redundancy="raid1")
        arr.zone_append(0, int32_blocks(2 * STRIPE, seed=5))
        kill_member(arr, 1)
        arr.replace_member(1, make_device())
        arr.begin_member_rebuild(0)
        arr.devices[1].submit_append(0, np.zeros(STRIPE * BLOCK,
                                                 np.uint8)).result()
        member, wp = arr.begin_member_rebuild(0)     # restart: re-parked
        assert arr.devices[1].zone(0).write_pointer == 0
        shard = arr.member_shard(1, arr.read_zone(0).reshape(-1, BLOCK))
        arr.devices[1].submit_append(0, shard).result()
        arr.commit_member_rebuild(0)
        assert arr.zone(0).is_writable

    def test_replace_refuses_pulling_live_data(self):
        """Swapping out a member that still holds the only copy (another
        member already offline under xor) must refuse atomically."""
        arr = make_array(4, redundancy="xor")
        arr.zone_append(0, int32_blocks(STRIPE * arr.data_columns))
        kill_member(arr, 0, zones=[0])
        with pytest.raises(ZoneStateError, match="unrecoverable"):
            arr.replace_member(2, make_device())
        assert arr.rebuilding_zones() == {}

    def test_replace_skips_already_lost_zones(self):
        arr = make_array(4, redundancy="xor")
        for z in (0, 1):
            arr.zone_append(z, int32_blocks(STRIPE * arr.data_columns))
        kill_member(arr, 0, zones=[0])
        kill_member(arr, 1, zones=[0, 1])      # zone 0 is now double-faulted
        pending = arr.replace_member(1, make_device())
        assert pending == [1]                  # zone 0 is gone, not pending
        assert arr.zone(0).state is ZoneState.OFFLINE

    def test_write_pointer_frozen_mid_rebuild(self):
        arr = make_array(2, redundancy="raid1")
        arr.zone_append(0, int32_blocks(STRIPE))
        kill_member(arr, 1)
        arr.replace_member(1, make_device())
        with pytest.raises(ZoneStateError, match="frozen"):
            arr.zone(0).write_pointer = 0


# ------------------------------------------------------ full lifecycle
LIFECYCLE_GRID = [("raid1", 2), ("raid1", 4), ("raid1", 8),
                  ("xor", 4), ("xor", 8)]


class TestFullLifecycle:
    @pytest.mark.parametrize("redundancy,n", LIFECYCLE_GRID)
    def test_kill_promote_rebuild_bit_identical(self, redundancy, n):
        """append → kill member → auto-promote via the alert path → rebuild
        → reads and offloads bit-identical, zones writable, scrub clean."""
        arr = make_array(n, num_zones=3, zone_kib=128, redundancy=redundancy)
        fills = [arr.zone_blocks, arr.zone_blocks // 2 + 3,
                 STRIPE * arr.data_columns + 1]
        for z, fill in enumerate(fills):
            arr.zone_append(z, int32_blocks(fill, seed=z))
        before = [arr.read_zone(z).copy() for z in range(3)]
        mon = ArrayHealthMonitor(arr)
        engine = AlertEngine(rules=[HealthPromotionRule(mon)])
        mgr = ArrayManager(arr, spares=[make_device(3, 128)], monitor=mon)
        mgr.attach(engine)
        mon.sample()
        victim = n - 1
        kill_member(arr, victim)
        fired = engine.evaluate()
        assert any(a.rule == "member_degraded" for a in fired)
        assert mgr.wait(timeout=60)
        st = mgr.status()[victim]
        assert st["state"] == "complete", st
        assert arr.rebuilding_zones() == {}
        for z in range(3):
            assert arr.zone(z).state is not ZoneState.READ_ONLY
            assert np.array_equal(arr.read_zone(z), before[z])
        assert arr.zone(1).is_writable
        arr.zone_append(1, int32_blocks(2, seed=42))
        res = mgr.scrub()
        assert res["mismatches"] == 0
        assert res["zones_scrubbed"] == 3
        # the incident resolves on the next evaluation (monitor rebound)
        engine.evaluate()
        keys = engine.active("member_degraded")["member_degraded"]
        assert not any(k.startswith(f"member{victim}/") for k in keys)

    def test_offload_bit_identity_through_scheduler_with_metering(self):
        """Offloads running concurrently with the rebuild return the healthy
        answer bit-identically, and the copy traffic is metered on the
        'rebuild' tenant (scrub on 'scrub')."""
        arr = make_array(4, num_zones=3, zone_kib=128, redundancy="xor")
        for z in range(3):
            arr.zone_append(z, int32_blocks(arr.zone_blocks - 5, seed=z))
        sched = OffloadScheduler(arr, default_tier="interp")
        sched.start()
        try:
            prog = filter_sum("int32", "ge", 0)
            healthy = [sched.run_and_fetch(prog, z)[0] for z in range(3)]
            mgr = ArrayManager(arr, scheduler=sched,
                               spares=[make_device(3, 128)])
            kill_member(arr, 2)
            assert mgr.promote_spare(2, reason="test")
            # live offloads while the rebuild copies
            during = [sched.run_and_fetch(prog, z)[0] for z in range(3)]
            assert mgr.wait(timeout=60)
            assert mgr.status()[2]["state"] == "complete"
            after = [sched.run_and_fetch(prog, z)[0] for z in range(3)]
            assert during == healthy
            assert after == healthy
            res = mgr.scrub()
            assert res["mismatches"] == 0
            ts = sched.tenant_stats()
            assert ts["rebuild"]["ops"] > 0
            assert ts["rebuild"]["bytes"] > 0
            assert ts["scrub"]["ops"] > 0
        finally:
            sched.close()

    def test_unattended_recovery_under_live_offloads(self):
        """A raid1 member dies and nobody calls promote_spare: the alert
        path promotes the spare, offloads issued while the rebuild is held
        between zones (zone 0 cut over, zones 1-2 still copying) return
        the healthy answers, and afterwards every zone is writable and
        bit-identical, scrubs find nothing, and the copy and scrub traffic
        were metered on their own tenants."""
        arr = make_array(2, num_zones=3, zone_kib=128, redundancy="raid1")
        for z in range(3):
            arr.zone_append(z, int32_blocks(arr.zone_blocks // 2 + 2 * z,
                                            seed=z))
        before = [arr.read_zone(z).copy() for z in range(3)]
        mon = ArrayHealthMonitor(arr)
        engine = AlertEngine(rules=[HealthPromotionRule(mon)])
        held, release = threading.Event(), threading.Event()

        def hold_after_first_cutover(e):
            # runs on the rebuild worker, outside the array lock
            if e.name == "array.zone_rebuilt" and not held.is_set():
                held.set()
                release.wait(timeout=60)

        prog = filter_sum("int32", "ge", 0)
        with OffloadScheduler(arr) as sched:
            sched.register_tenant("reader")
            healthy = [sched.run_and_fetch(prog, z, tenant="reader")[0]
                       for z in range(3)]
            mgr = ArrayManager(arr, scheduler=sched, monitor=mon,
                               spares=[make_device(3, 128)], rows_per_io=1)
            mgr.attach(engine)
            mon.sample()
            assert engine.evaluate() == []
            seq0 = event_log().last_seq()
            unsub = event_log().subscribe(hold_after_first_cutover)
            try:
                kill_member(arr, 1)
                fired = engine.evaluate()
                assert any(a.rule == "member_degraded" for a in fired)
                assert event_log().snapshot(name="spare.promoted",
                                            since_seq=seq0)
                assert held.wait(timeout=60), "no zone was rebuilt"
                assert sorted(arr.rebuilding_zones()) == [1, 2]
                assert mgr.status()[1]["state"] == "running"
                during = [sched.run_and_fetch(prog, z, tenant="reader")[0]
                          for z in range(3)]
            finally:
                release.set()
                unsub()
            assert during == healthy
            assert mgr.wait(timeout=60)
            assert mgr.status()[1]["state"] == "complete"
            for z in range(3):
                assert arr.zone(z).is_writable
                assert np.array_equal(arr.read_zone(z), before[z])
            assert mgr.scrub()["mismatches"] == 0
            after = [sched.run_and_fetch(prog, z, tenant="reader")[0]
                     for z in range(3)]
            assert after == healthy
            for z in range(3):
                arr.zone_append(z, int32_blocks(1, seed=9))
            assert mgr.scrub()["mismatches"] == 0
            ts = sched.tenant_stats()
            assert ts["rebuild"]["ops"] > 0 and ts["rebuild"]["bytes"] > 0
            assert ts["scrub"]["ops"] > 0

    def test_promotion_is_idempotent(self):
        arr = make_array(2, num_zones=2, zone_kib=128, redundancy="raid1")
        arr.zone_append(0, int32_blocks(arr.zone_blocks, seed=1))
        mon = ArrayHealthMonitor(arr)
        engine = AlertEngine(rules=[HealthPromotionRule(mon)])
        mgr = ArrayManager(arr, spares=[make_device(2, 128),
                                        make_device(2, 128)], monitor=mon)
        mgr.attach(engine)
        mon.sample()
        kill_member(arr, 0)
        engine.evaluate()
        # alert re-fire / duplicated evaluation: no double promotion
        engine.evaluate()
        assert mgr.promote_spare(0) is False      # live rebuild: refused
        assert mgr.wait(timeout=60)
        assert mgr.status()[0]["state"] == "complete"
        assert mgr.spare_count == 1               # exactly ONE spare consumed

    def test_promotion_without_spares_reports_exhaustion(self):
        arr = make_array(2, num_zones=2, zone_kib=128, redundancy="raid1")
        arr.zone_append(0, int32_blocks(STRIPE))
        kill_member(arr, 0)
        mgr = ArrayManager(arr)
        assert mgr.promote_spare(0) is False
        assert event_log().snapshot(name="spare.exhausted")


# ----------------------------------------------------- faults mid-rebuild
class TestFaultsMidRebuild:
    def test_spare_death_mid_rebuild_restarts_onto_next_spare(self):
        """The spare dies after the first zone commits: the rebuild swaps
        in the next spare (committed zones re-enter the pending set) and
        still converges to bit-identical, fully writable zones."""
        arr = make_array(2, num_zones=3, zone_kib=128, redundancy="raid1")
        for z in range(3):
            arr.zone_append(z, int32_blocks(arr.zone_blocks // 2, seed=z))
        before = [arr.read_zone(z).copy() for z in range(3)]
        victim = 1
        kill_member(arr, victim)
        spare1 = make_device(3, 128)
        mgr = ArrayManager(arr, spares=[spare1, make_device(3, 128)])
        killed = threading.Event()
        # deterministic injection point: the moment the FIRST zone cuts
        # over, every further write to the spare fails (it died)
        orig_append = spare1.submit_append

        def dying_append(zone_id, data):
            if killed.is_set():
                raise ZNSError("injected: spare lost power mid-rebuild")
            return orig_append(zone_id, data)

        spare1.submit_append = dying_append

        def on_event(e):
            if e.name == "array.zone_rebuilt":
                killed.set()

        unsub = event_log().subscribe(on_event)
        try:
            assert mgr.promote_spare(victim)
            assert mgr.wait(timeout=60)
        finally:
            unsub()
        st = mgr.status()[victim]
        assert killed.is_set()
        assert st["restarts"] == 1, st
        assert st["state"] == "complete", st
        assert mgr.spare_count == 0
        for z in range(3):
            assert arr.zone(z).is_writable
            assert np.array_equal(arr.read_zone(z), before[z])
        assert event_log().snapshot(name="rebuild.restarted")

    def test_spare_death_with_empty_pool_degrades_cleanly(self):
        arr = make_array(2, num_zones=2, zone_kib=128, redundancy="raid1")
        for z in range(2):
            arr.zone_append(z, int32_blocks(arr.zone_blocks // 2, seed=z))
        before = [arr.read_zone(z).copy() for z in range(2)]
        kill_member(arr, 0)
        spare = make_device(2, 128)
        mgr = ArrayManager(arr, spares=[spare])
        killed = threading.Event()
        orig_append = spare.submit_append

        def dying_append(zone_id, data):
            if killed.is_set():
                raise ZNSError("injected: spare lost power mid-rebuild")
            return orig_append(zone_id, data)

        spare.submit_append = dying_append

        def on_event(e):
            if e.name == "array.zone_rebuilt":
                killed.set()

        unsub = event_log().subscribe(on_event)
        try:
            assert mgr.promote_spare(0)
            assert mgr.wait(timeout=60)
        finally:
            unsub()
        st = mgr.status()[0]
        assert st["state"] == "failed", st
        assert event_log().snapshot(name="rebuild.failed")
        # survivors still serve every committed byte
        for z in range(2):
            assert np.array_equal(arr.read_zone(z), before[z])

    def test_xor_double_fault_mid_rebuild_goes_offline_not_corrupt(self):
        """A survivor dies while a zone's rebuild still needs it: that zone
        is abandoned OFFLINE (never half-rebuilt data), other zones keep
        rebuilding, and the worker terminates — no hang."""
        arr = make_array(4, num_zones=3, zone_kib=128, redundancy="xor")
        for z in range(3):
            arr.zone_append(z, int32_blocks(arr.zone_blocks // 2, seed=z))
        before = [arr.read_zone(z).copy() for z in range(3)]
        victim, survivor = 1, 3
        kill_member(arr, victim)
        mgr = ArrayManager(arr, spares=[make_device(3, 128)])
        tripped = threading.Event()

        def on_event(e):
            if e.name == "array.zone_rebuilt" and not tripped.is_set():
                tripped.set()
                nxt = sorted(arr.rebuilding_zones())[0]
                arr.devices[survivor].set_offline(nxt)

        unsub = event_log().subscribe(on_event)
        try:
            assert mgr.promote_spare(victim)
            assert mgr.wait(timeout=60)      # bounded: no hang
        finally:
            unsub()
        st = mgr.status()[victim]
        assert tripped.is_set()
        assert st["state"] == "degraded", st
        assert len(st["zones_failed"]) == 1
        dead = st["zones_failed"][0]
        assert arr.zone(dead).state is ZoneState.OFFLINE
        with pytest.raises(ZoneStateError):
            arr.read_zone(dead)              # clean error, not garbage
        for z in range(3):
            if z != dead:
                assert arr.zone(z).is_writable
                assert np.array_equal(arr.read_zone(z), before[z])
        assert event_log().snapshot(name="rebuild.zone_failed")

    def test_checkpoint_restores_mid_rebuild(self, tmp_path):
        """A striped checkpoint restore riding a mid-rebuild array (zones
        marked, reads degraded) is bit-identical — and again after the
        rebuild commits."""
        rng = np.random.default_rng(3)
        tree = {"w": rng.standard_normal((64, 64)).astype(np.float32),
                "b": rng.integers(-5, 5, 4096, dtype=np.int64)}
        like = {"w": np.zeros((64, 64), np.float32),
                "b": np.zeros(4096, np.int64)}
        store = ZonedCheckpointStore.striped(
            tmp_path, num_devices=3, num_zones=6,
            member_zone_bytes=64 * 4096, stripe_blocks=4, redundancy="xor")
        store.save(1, tree)
        store.flush()
        arr = store.device
        kill_member(arr, 1)
        mgr = ArrayManager(arr, spares=[ZonedDevice(
            num_zones=6, zone_bytes=64 * 4096, block_bytes=BLOCK)])
        assert mgr.promote_spare(1)
        got = store.restore(like=like)       # races the rebuild by design
        assert np.array_equal(got["w"], tree["w"])
        assert np.array_equal(got["b"], tree["b"])
        assert mgr.wait(timeout=60)
        assert mgr.status()[1]["state"] == "complete"
        got2 = store.restore(like=like)
        assert np.array_equal(got2["w"], tree["w"])
        assert np.array_equal(got2["b"], tree["b"])
        assert mgr.scrub()["mismatches"] == 0


# ----------------------------------------------------------------- scrub
class TestScrub:
    def test_clean_array_scrubs_clean(self):
        arr = make_array(4, num_zones=2, zone_kib=128, redundancy="xor")
        arr.zone_append(0, int32_blocks(arr.zone_blocks, seed=1))
        mgr = ArrayManager(arr)
        res = mgr.scrub()
        assert res["mismatches"] == 0 and res["rows_verified"] > 0

    def test_raid1_mirror_divergence_detected_and_feeds_health(self):
        arr = make_array(2, num_zones=2, zone_kib=128, redundancy="raid1")
        arr.zone_append(0, int32_blocks(arr.zone_blocks // 2, seed=2))
        mon = ArrayHealthMonitor(arr)
        mon.sample()
        corrupt_block(arr.devices[1], 0, 5)
        mgr = ArrayManager(arr, monitor=mon)
        res = mgr.scrub()
        assert res["mismatches"] == 1
        assert arr.devices[1].metrics.counter("scrub_mismatches").value == 1
        ev = event_log().snapshot(name="scrub.mismatch")
        assert ev and ev[-1].tags["zone"] == 0
        assert mon.members[1].sample() >= HealthStatus.SUSPECT
        assert mon.members[1].smart_log()["scrub_mismatches"] == 1

    def test_xor_parity_rot_detected_on_full_row(self):
        arr = make_array(4, num_zones=2, zone_kib=128, redundancy="xor")
        row = STRIPE * arr.data_columns
        arr.zone_append(0, int32_blocks(3 * row, seed=3))
        # corrupt the rotating parity member of row 1
        _data, parity = arr._row_devices(1)
        corrupt_block(arr.devices[parity], 0, STRIPE + 1)
        res = ArrayManager(arr).scrub()
        assert res["mismatches"] == 1
        assert event_log().snapshot(name="scrub.mismatch")[-1].tags["row"] == 1

    def test_xor_tail_row_checked_against_accumulator(self):
        arr = make_array(4, num_zones=2, zone_kib=128, redundancy="xor")
        row = STRIPE * arr.data_columns
        arr.zone_append(0, int32_blocks(2 * row + STRIPE + 2, seed=4))
        data_devs, _parity = arr._row_devices(2)
        corrupt_block(arr.devices[data_devs[0]], 0, 2 * STRIPE)   # tail chunk
        res = ArrayManager(arr).scrub()
        assert res["mismatches"] == 1
        assert "tail" in event_log().snapshot(name="scrub.mismatch")[-1].message

    def test_scrub_skips_degraded_and_rebuilding_zones(self):
        arr = make_array(2, num_zones=3, zone_kib=128, redundancy="raid1")
        for z in range(2):
            arr.zone_append(z, int32_blocks(STRIPE, seed=z))
        kill_member(arr, 0, zones=[0])
        res = ArrayManager(arr).scrub()
        assert res["zones_skipped"] == 1
        assert res["zones_scrubbed"] == 1

    def test_raid0_has_nothing_to_scrub(self):
        arr = make_array(2, num_zones=2, zone_kib=128, redundancy="raid0")
        res = ArrayManager(arr).scrub()
        assert res["zones_scrubbed"] == 0 and res["zones_skipped"] == 2
