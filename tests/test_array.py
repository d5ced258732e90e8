"""CSD array subsystem: stripe round-trips (all redundancy modes), queue
arbitration/backpressure, scheduler result-equivalence vs the single-device
NvmCsd oracle for every OpCode terminal, degraded-read reconstruction
bit-identity under raid1/xor, and the fault paths: mid-fan-out member death,
leaked-future regression, torn-append fencing, locked zone transitions."""
import gc
import math
import threading
import time
import weakref

import numpy as np
import pytest

from repro.array import scheduler as scheduler_mod
from repro.array import (
    ArrayOffloadError,
    Completion,
    OffloadCommand,
    OffloadScheduler,
    QueueFullError,
    QueuePair,
    CompletionQueue,
    StripedZoneArray,
    SubmissionQueue,
    WeightedRoundRobinArbiter,
)
from repro.core import CsdTier, NvmCsd, VerifyError
from repro.core.vm import run_oracle
from repro.core.programs import (
    Instruction,
    OpCode,
    Program,
    field_reduce,
    filter_count,
    filter_select,
    filter_sum,
    histogram,
    select_records,
)
from repro.telemetry import trace
from repro.telemetry.metrics import registry
from repro.zns import (
    OutOfBoundsError,
    ZonedDevice,
    ZoneFullError,
    ZoneState,
    ZoneStateError,
)

BLOCK = 4096
STRIPE = 4


def make_array(n_devices, *, num_zones=4, zone_kib=256, stripe=STRIPE,
               redundancy="raid0", **device_kw):
    devs = [ZonedDevice(num_zones=num_zones, zone_bytes=zone_kib * 1024,
                        block_bytes=BLOCK, **device_kw)
            for _ in range(n_devices)]
    return StripedZoneArray(devs, stripe_blocks=stripe, redundancy=redundancy)


def int32_blocks(n_blocks, seed=0, lo=-1000, hi=1000):
    rng = np.random.default_rng(seed)
    return rng.integers(lo, hi, n_blocks * BLOCK // 4, dtype=np.int32)


# ------------------------------------------------------------------ striping

@pytest.mark.parametrize("n_devices", [1, 2, 3, 4])
def test_stripe_append_read_round_trip(n_devices):
    arr = make_array(n_devices)
    data = int32_blocks(4 * STRIPE * n_devices + 7)  # force a partial chunk
    arr.zone_append(0, data)
    back = np.frombuffer(arr.read_blocks(0, 0, arr.zone(0).write_pointer)
                         .tobytes(), np.int32)
    assert np.array_equal(back, data)


def test_stripe_partial_reads_any_offset():
    arr = make_array(3)
    data = int32_blocks(23)
    arr.zone_append(0, data)
    per_block = BLOCK // 4
    for off, n in [(0, 1), (1, 5), (3, 17), (7, 16), (22, 1), (0, 23)]:
        back = np.frombuffer(arr.read_blocks(0, off, n).tobytes(), np.int32)
        assert np.array_equal(back, data[off * per_block:(off + n) * per_block])


def test_stripe_incremental_appends_interleave_correctly():
    arr = make_array(2)
    parts = [int32_blocks(n, seed=n) for n in (3, 1, 6, 2)]
    for p in parts:
        arr.zone_append(0, p)
    want = np.concatenate(parts)
    back = np.frombuffer(arr.read_zone(0).tobytes(), np.int32)
    assert np.array_equal(back, want)
    # data really is spread over both members
    assert all(d.zone(0).write_pointer > 0 for d in arr.devices)


def test_stripe_reset_and_reuse():
    arr = make_array(2)
    arr.zone_append(1, int32_blocks(8))
    arr.reset_zone(1)
    assert arr.zone(1).write_pointer == 0
    assert all(d.zone(1).write_pointer == 0 for d in arr.devices)
    fresh = int32_blocks(4, seed=9)
    arr.zone_append(1, fresh)
    assert np.array_equal(
        np.frombuffer(arr.read_zone(1).tobytes(), np.int32), fresh)


def test_stripe_bounds_and_capacity_errors():
    arr = make_array(2, zone_kib=64)  # 16 blocks/member -> 32 logical
    arr.zone_append(0, int32_blocks(4))
    with pytest.raises(OutOfBoundsError):
        arr.read_blocks(0, 0, 5)   # beyond logical write pointer
    with pytest.raises(ZoneFullError):
        arr.zone_append(0, int32_blocks(29))  # exceeds logical capacity
    with pytest.raises(ValueError):
        StripedZoneArray([ZonedDevice(num_zones=2, zone_bytes=64 * 1024),
                          ZonedDevice(num_zones=4, zone_bytes=64 * 1024)])


def test_logical_write_pointer_setter_distributes():
    arr = make_array(3, stripe=4)
    z = arr.zone(0)
    z.write_pointer = 4 * 3 * 2 + 4 + 2   # 2 full rows + 1 full chunk + 2
    assert [d.zone(0).write_pointer for d in arr.devices] == [12, 10, 8]
    assert z.write_pointer == 30
    z.write_pointer = 0
    assert all(d.zone(0).write_pointer == 0 for d in arr.devices)


# -------------------------------------------------------------------- queues

def test_sq_backpressure_rejects_then_unblocks():
    sq = SubmissionQueue("t", depth=2)
    prog = filter_count("int32", "gt", 0)
    mk = lambda: OffloadCommand(prog, 0, 0, 4, None)
    sq.submit(mk()); sq.submit(mk())
    with pytest.raises(QueueFullError):
        sq.submit(mk())
    assert sq.rejected == 1
    # a blocked submitter proceeds once the arbiter pops a slot
    done = threading.Event()
    def blocked():
        sq.submit(mk(), block=True, timeout=5.0)
        done.set()
    t = threading.Thread(target=blocked); t.start()
    assert not done.wait(0.05)
    assert sq.pop() is not None
    assert done.wait(5.0)
    t.join()
    assert len(sq) == 2


def test_wrr_arbiter_respects_weights():
    prog = filter_count("int32", "gt", 0)
    pairs = {}
    arb = WeightedRoundRobinArbiter()
    for tenant, weight in [("a", 2), ("b", 1)]:
        pair = QueuePair(SubmissionQueue(tenant, depth=16, weight=weight),
                         CompletionQueue(tenant))
        for _ in range(6):
            pair.sq.submit(OffloadCommand(prog, 0, 0, 4, None, tenant=tenant))
        pairs[tenant] = pair
        arb.add(pair)
    order = []
    while (nxt := arb.next_command()) is not None:
        order.append(nxt[0].tenant)
    # 2:1 service mix while both queues are backlogged; once 'a' drains the
    # arbiter stays work-conserving and serves the remaining 'b' commands
    assert order == ["a", "a", "b"] * 3 + ["b", "b", "b"]


def test_wrr_arbiter_work_conserving_when_queue_empty():
    prog = filter_count("int32", "gt", 0)
    arb = WeightedRoundRobinArbiter()
    a = QueuePair(SubmissionQueue("a", depth=4, weight=3), CompletionQueue("a"))
    b = QueuePair(SubmissionQueue("b", depth=4, weight=1), CompletionQueue("b"))
    arb.add(a); arb.add(b)
    b.sq.submit(OffloadCommand(prog, 0, 0, 4, None, tenant="b"))
    nxt = arb.next_command()
    assert nxt is not None and nxt[0].tenant == "b"
    assert arb.next_command() is None


# ----------------------------------------------------------------- scheduler

def oracle_pair(n_blocks, seed=0):
    """(single-device NvmCsd, striped 4-wide scheduler) over identical data."""
    data = int32_blocks(n_blocks, seed=seed)
    dev = ZonedDevice(num_zones=2, zone_bytes=1024 * 1024, block_bytes=BLOCK)
    dev.zone_append(0, data)
    arr = make_array(4)
    arr.zone_append(0, data)
    return NvmCsd(dev), OffloadScheduler(arr)


TERMINAL_PROGRAMS = [
    filter_count("int32", "gt", 0),
    filter_sum("int32", "lt", 100),
    field_reduce("int32", 8, 1, "min"),
    field_reduce("int32", 8, 2, "max"),
    histogram("int32", -1000, 1000, 32),
    filter_select("int32", "gt", 900, 64),
    select_records("int32", 8, 0, "gt", 500, 32),
]


@pytest.mark.parametrize("program", TERMINAL_PROGRAMS,
                         ids=[p.name for p in TERMINAL_PROGRAMS])
def test_scheduler_matches_single_device_oracle(program):
    csd, sched = oracle_pair(40)
    want, _ = csd.run_and_fetch(program, 0)
    got, stats = sched.run_and_fetch(program, 0)
    if isinstance(want, tuple):
        assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
        assert int(want[1]) == int(got[1])
    else:
        assert np.asarray(want).dtype == np.asarray(got).dtype
        assert np.array_equal(np.asarray(want), np.asarray(got))
    assert stats.n_devices == 4
    assert stats.n_chunks == 10
    assert stats.bytes_read == 40 * BLOCK


@pytest.mark.parametrize("tier", [CsdTier.INTERP, CsdTier.JIT, CsdTier.KERNEL])
def test_scheduler_tiers_agree_with_tail_chunk(tier):
    csd, sched = oracle_pair(37, seed=3)  # 37 blocks -> partial tail chunk
    program = filter_count("int32", "gt", 0)
    want, _ = csd.run_and_fetch(program, 0, tier=tier)
    got, _ = sched.run_and_fetch(program, 0, tier=tier)
    assert int(want) == int(got)


def test_scheduler_batches_full_chunks_on_jit_tier():
    _, sched = oracle_pair(40)
    stats = sched.nvm_cmd_bpf_run(filter_count("int32", "gt", 0), 0)
    # 10 chunks over 4 devices: the 2-chunk devices batch via vmap
    assert stats.batched_chunks > 0
    assert stats.tier == CsdTier.JIT


def test_scheduler_partial_extent_matches_oracle():
    csd, sched = oracle_pair(40, seed=7)
    program = filter_sum("int32", "ge", -50)
    want, _ = csd.run_and_fetch(program, 0, block_off=4, n_blocks=24)
    got, _ = sched.run_and_fetch(program, 0, block_off=4, n_blocks=24)
    assert int(want) == int(got)


def test_scheduler_verifies_before_enqueue():
    _, sched = oracle_pair(8)
    bad = Program("int32", (Instruction(OpCode.CMP_GT, 0),), name="no_terminal")
    with pytest.raises(VerifyError):
        sched.submit(bad, 0)
    assert len(sched.queue_pair().sq) == 0  # rejected work never queues


def test_scheduler_single_device_degenerate_path():
    data = int32_blocks(12, seed=5)
    dev = ZonedDevice(num_zones=2, zone_bytes=1024 * 1024, block_bytes=BLOCK)
    dev.zone_append(0, data)
    arr = StripedZoneArray(
        [ZonedDevice(num_zones=2, zone_bytes=1024 * 1024, block_bytes=BLOCK)],
        stripe_blocks=STRIPE)
    arr.zone_append(0, data)
    program = filter_count("int32", "le", 250)
    want, _ = NvmCsd(dev).run_and_fetch(program, 0)
    got, stats = OffloadScheduler(arr).run_and_fetch(program, 0)
    assert int(want) == int(got)
    assert stats.n_devices == 1


def test_scheduler_offline_member_degrades_with_clear_error():
    _, sched = oracle_pair(40)
    sched.array.set_offline(0, device=2)
    with pytest.raises(ArrayOffloadError, match="member device 2"):
        sched.nvm_cmd_bpf_run(filter_count("int32", "gt", 0), 0)
    # the failure is also visible on the completion queue, not just raised
    comps = sched.queue_pair().cq.drain()
    assert comps and not comps[-1].ok


def test_scheduler_async_dispatcher_and_wait():
    csd, sched = oracle_pair(40, seed=11)
    program = filter_sum("int32", "lt", 0)
    want, _ = csd.run_and_fetch(program, 0)
    sched.start()
    try:
        cmd_ids = [sched.submit(program, 0) for _ in range(3)]
        comps = [sched.wait(cid, timeout=60) for cid in cmd_ids]
    finally:
        sched.stop()
    assert all(c.ok for c in comps)
    assert all(int(c.value) == int(want) for c in comps)


# ------------------------------------------------ redundancy & fault paths

REDUNDANT = [("raid1", 2), ("raid1", 4), ("xor", 3), ("xor", 4)]


@pytest.mark.parametrize("mode,n", REDUNDANT)
def test_redundant_append_read_round_trip(mode, n):
    arr = make_array(n, redundancy=mode)
    data = int32_blocks(4 * STRIPE * arr.data_columns + 7)  # partial chunk
    arr.zone_append(0, data)
    back = np.frombuffer(arr.read_zone(0).tobytes(), np.int32)
    assert np.array_equal(back, data)
    # incremental appends interleave correctly too (exercises the xor
    # tail-row parity accumulator across append boundaries)
    arr2 = make_array(n, redundancy=mode)
    parts = [int32_blocks(k, seed=10 + k) for k in (3, 1, 6, 2, 11)]
    for p in parts:
        arr2.zone_append(0, p)
    want = np.concatenate(parts)
    assert np.array_equal(
        np.frombuffer(arr2.read_zone(0).tobytes(), np.int32), want)


def test_redundancy_geometry_validation():
    mk = lambda n: [ZonedDevice(num_zones=2, zone_bytes=64 * 1024,
                                block_bytes=BLOCK) for _ in range(n)]
    with pytest.raises(ValueError, match="even member count"):
        StripedZoneArray(mk(3), stripe_blocks=4, redundancy="raid1")
    with pytest.raises(ValueError, match=">= 3 members"):
        StripedZoneArray(mk(2), stripe_blocks=4, redundancy="xor")
    with pytest.raises(ValueError, match="redundancy"):
        StripedZoneArray(mk(2), stripe_blocks=4, redundancy="raid6")
    # capacity: raid1 halves, xor spends one member on parity
    assert StripedZoneArray(mk(4), stripe_blocks=4,
                            redundancy="raid1").zone_blocks == 2 * 16
    assert StripedZoneArray(mk(4), stripe_blocks=4,
                            redundancy="xor").zone_blocks == 3 * 16


@pytest.mark.parametrize("mode,n", REDUNDANT)
def test_degraded_read_bit_identical_for_every_dead_member(mode, n):
    data = int32_blocks(37, seed=1)
    per_block = BLOCK // 4
    for dead in range(n):
        arr = make_array(n, redundancy=mode)
        arr.zone_append(0, data)
        arr.set_offline(0, device=dead)
        assert arr.zone(0).degraded
        got = np.frombuffer(arr.read_blocks(0, 0, 37).tobytes(), np.int32)
        assert np.array_equal(got, data), f"{mode} dead member {dead}"
        for off, k in [(0, 1), (1, 5), (3, 17), (7, 16), (36, 1), (5, 32)]:
            g = np.frombuffer(arr.read_blocks(0, off, k).tobytes(), np.int32)
            assert np.array_equal(
                g, data[off * per_block:(off + k) * per_block])
        assert arr.stats["degraded_reads"] > 0, f"{mode} dead member {dead}"


def test_raid0_offline_member_stays_fatal():
    arr = make_array(3)
    arr.zone_append(0, int32_blocks(12))
    arr.set_offline(0, device=1)
    assert arr.zone(0).state == ZoneState.OFFLINE
    with pytest.raises(ZoneStateError):
        arr.read_blocks(0, 0, 12)


@pytest.mark.parametrize("mode,n", [("raid1", 2), ("xor", 3)])
def test_degraded_reconstruction_rides_the_ring(mode, n):
    """Emulated members: reconstruction reads are reactor-retired transfers
    (no extra threads), and the reconstructed bytes stay bit-identical."""
    arr = make_array(n, redundancy=mode, read_us_per_block=5.0)
    data = int32_blocks(45, seed=2)
    arr.zone_append(0, data)
    arr.set_offline(0, device=0)
    fut = arr.submit_read(0, 0, 45, dtype=np.int32)
    assert np.array_equal(np.asarray(fut.result(timeout=20)), data)
    assert arr.stats["degraded_reads"] > 0


@pytest.mark.parametrize("mode,n", [("raid0", 2), ("raid1", 2), ("xor", 3)])
def test_member_death_between_submit_and_completion(mode, n):
    """A member going OFFLINE while its transfers are in flight must not
    corrupt or hang them: the extent was snapshotted at submission (the ZNS
    contract), so the aggregate retires with the correct bytes."""
    arr = make_array(n, redundancy=mode, read_us_per_block=20.0)
    data = int32_blocks(32, seed=3)
    arr.zone_append(0, data)
    fut = arr.submit_read(0, 0, 32, dtype=np.int32)
    arr.set_offline(0, device=n - 1)          # dies mid-flight
    assert np.array_equal(np.asarray(fut.result(timeout=20)), data)


def test_raid1_round_robin_spreads_healthy_reads():
    arr = make_array(2, redundancy="raid1")
    arr.zone_append(0, int32_blocks(8 * STRIPE))
    for d in arr.devices:
        d.stats["blocks_read"] = 0
    arr.read_zone(0)
    reads = [d.stats["blocks_read"] for d in arr.devices]
    assert all(r > 0 for r in reads), f"mirror pair not round-robined: {reads}"
    assert sum(reads) == 8 * STRIPE           # each block read exactly once


def test_xor_parity_chunk_is_xor_of_row_data():
    """White-box: after full stripe rows land, the rotating parity member
    holds the XOR of the row's data chunks."""
    arr = make_array(3, redundancy="xor")
    s, C = arr.stripe_blocks, arr.data_columns
    data = int32_blocks(3 * C * s, seed=4)     # 3 complete rows
    arr.zone_append(0, data)
    blocks = np.frombuffer(data.tobytes(), np.uint8).reshape(-1, BLOCK)
    for row in range(3):
        data_devs, parity = arr._row_devices(row)
        want = np.zeros((s, BLOCK), np.uint8)
        for col, d in enumerate(data_devs):
            chunk = row * C + col
            want ^= blocks[chunk * s:(chunk + 1) * s]
        got = arr.devices[parity].read_blocks(0, row * s, s)
        assert np.array_equal(got.reshape(-1, BLOCK), want), f"row {row}"


def test_unrecoverable_member_loss_goes_offline():
    arr = make_array(4, redundancy="raid1")
    arr.zone_append(0, int32_blocks(16))
    arr.set_offline(0, device=0)
    arr.set_offline(0, device=1)               # both partners of column 0
    assert arr.zone(0).state == ZoneState.OFFLINE
    with pytest.raises(ZoneStateError):
        arr.read_blocks(0, 0, 16)
    # offloads keep the PR 2 clean-error contract even past the redundancy
    # limit: ArrayOffloadError, not a raw ZoneStateError
    with pytest.raises(ArrayOffloadError, match="unrecoverable"):
        OffloadScheduler(arr).nvm_cmd_bpf_run(filter_count("int32", "gt", 0), 0)
    arr2 = make_array(3, redundancy="xor")
    arr2.zone_append(0, int32_blocks(16))
    arr2.set_offline(0, device=0)
    arr2.set_offline(0, device=2)              # two dead under single parity
    assert arr2.zone(0).state == ZoneState.OFFLINE


def test_degraded_zone_is_read_only():
    arr = make_array(2, redundancy="raid1")
    data = int32_blocks(12, seed=5)
    arr.zone_append(0, data)
    arr.set_offline(0, device=1)
    assert arr.zone(0).state == ZoneState.READ_ONLY
    with pytest.raises(ZoneStateError):
        arr.zone_append(0, int32_blocks(4))
    with pytest.raises(ZoneStateError, match="rebuild"):
        arr.reset_zone(0)
    assert np.array_equal(
        np.frombuffer(arr.read_zone(0).tobytes(), np.int32), data)


def test_submit_read_mid_fanout_failure_fails_aggregate_not_hangs():
    """Regression (leaked member futures): a member submit raising partway
    through the fan-out must retire the aggregate with the error — never
    orphan it."""
    arr = make_array(3, read_us_per_block=10.0)
    data = int32_blocks(24, seed=6)
    arr.zone_append(0, data)

    def boom(*a, **kw):
        raise ZoneStateError("injected: member died between check and submit")

    arr.devices[1].submit_read = boom
    fut = arr.submit_read(0, 0, 24)
    with pytest.raises(ZoneStateError, match="injected"):
        fut.result(timeout=10)                 # retires with the error


def test_submit_append_mid_fanout_failure_fails_and_fences():
    arr = make_array(3, append_us_per_block=10.0)

    def boom(*a, **kw):
        raise ZoneStateError("injected append death")

    arr.devices[1].submit_append = boom
    fut = arr.submit_append(0, int32_blocks(24, seed=7))
    with pytest.raises(ZoneStateError, match="injected"):
        fut.result(timeout=10)
    # member 0 landed its share, member 1 did not: the zone is torn — fenced
    # READ_ONLY until reset, and the logical write pointer never advanced
    assert arr.zone(0).write_pointer == 0
    assert arr.zone(0).state == ZoneState.READ_ONLY
    with pytest.raises(ZoneStateError):
        arr.zone_append(0, int32_blocks(4))
    del arr.devices[1].submit_append           # un-patch: reset recovers
    arr.reset_zone(0)
    assert arr.zone(0).is_writable
    data = int32_blocks(8, seed=8)
    arr.zone_append(0, data)
    assert np.array_equal(
        np.frombuffer(arr.read_zone(0).tobytes(), np.int32), data)


def test_finish_zone_partial_transition_raises_zone_state_error():
    """Regression (unlocked transitions): a member refusing a transition
    mid-loop surfaces as ZoneStateError instead of silently leaving the
    members in mixed states."""
    arr = make_array(3)
    arr.zone_append(0, int32_blocks(8))
    arr.devices[2].set_read_only(0)            # member 2 will refuse FINISH
    with pytest.raises(ZoneStateError, match="partial finish"):
        arr.finish_zone(0)
    # offline LOGICAL zone is guarded up front, like reset_zone
    arr.set_offline(1)
    with pytest.raises(ZoneStateError, match="offline"):
        arr.finish_zone(1)
    with pytest.raises(ZoneStateError, match="offline"):
        arr.set_read_only(1)


def test_finish_zone_on_degraded_array_transitions_survivors():
    arr = make_array(2, redundancy="raid1")
    arr.zone_append(0, int32_blocks(8, seed=9))
    arr.set_offline(0, device=0)
    arr.finish_zone(0)                         # survivors seal; no raise
    assert arr.devices[1].zone(0).state == ZoneState.FULL
    assert arr.devices[0].zone(0).state == ZoneState.OFFLINE


def test_xor_recovery_with_dead_member_never_fabricates_tail_bytes():
    """Regression: write-pointer recovery on an already-degraded xor array
    cannot rebuild the tail-row parity accumulator (the dead member's tail
    data is gone and its parity never landed) — tail reads must RAISE, not
    return zero bytes; complete rows still reconstruct bit-identically."""
    arr = make_array(3, redundancy="xor")
    s, C = arr.stripe_blocks, arr.data_columns
    data = int32_blocks(2 * C * s + 3, seed=14)     # 2 full rows + 3-block tail
    arr.zone_append(0, data)
    wp = arr.zone(0).write_pointer
    # the tail row's first data chunk lives on a data member — kill it, then
    # run the documented checkpoint-recovery path (write_pointer setter)
    tail_dev = arr._row_devices(2)[0][0]
    arr.set_offline(0, device=tail_dev)
    arr.zone(0).write_pointer = wp
    # complete rows: still exact
    got = np.frombuffer(arr.read_blocks(0, 0, 2 * C * s).tobytes(), np.int32)
    assert np.array_equal(got, data[: 2 * C * s * (BLOCK // 4)])
    # tail row: lost for the dead member — loud error, never zeros
    with pytest.raises(ZoneStateError, match="unrecoverable"):
        arr.read_blocks(0, 0, wp)
    # recovery while HEALTHY then losing the member stays exact (the
    # accumulator was rebuilt from live members before the failure)
    arr2 = make_array(3, redundancy="xor")
    arr2.zone_append(0, data)
    arr2.zone(0).write_pointer = wp
    arr2.set_offline(0, device=arr2._row_devices(2)[0][0])
    got = np.frombuffer(arr2.read_blocks(0, 0, wp).tobytes(), np.int32)
    assert np.array_equal(got, data)


def test_gather_pool_threads_are_daemonic():
    arr = make_array(2, read_us_per_block=5.0)
    arr.zone_append(0, int32_blocks(16))
    arr.read_zone(0)                           # routes through the pool
    gather = [t for t in threading.enumerate()
              if t.name.startswith("stripe-gather")]
    assert all(t.daemon for t in gather)


# --------------------------------------- scheduler over degraded arrays

@pytest.mark.parametrize("mode,n", [("raid1", 2), ("raid1", 4), ("xor", 3)])
def test_scheduler_degraded_offload_bit_identical(mode, n):
    """Acceptance: with one member zone OFFLINE, an offload over the
    degraded array returns the same result as over a single device, and the
    degraded fan-out is counted."""
    data = int32_blocks(40, seed=11)
    dev = ZonedDevice(num_zones=2, zone_bytes=1024 * 1024, block_bytes=BLOCK)
    dev.zone_append(0, data)
    csd = NvmCsd(dev)
    arr = make_array(n, redundancy=mode, zone_kib=1024)
    arr.zone_append(0, data)
    sched = OffloadScheduler(arr)
    for program in (filter_count("int32", "gt", 0),
                    filter_sum("int32", "lt", 100),
                    filter_select("int32", "gt", 900, 64)):
        want, _ = csd.run_and_fetch(program, 0)
        healthy, h_stats = sched.run_and_fetch(program, 0)
        assert h_stats.degraded_reads == 0
        arr.set_offline(0, device=0)
        degraded, d_stats = sched.run_and_fetch(program, 0)
        assert d_stats.degraded_reads > 0
        for got in (healthy, degraded):
            if isinstance(want, tuple):
                assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
                assert int(want[1]) == int(got[1])
            else:
                assert np.array_equal(np.asarray(want), np.asarray(got))
        # back to healthy for the next program's healthy pass
        for z in range(arr.num_zones):
            arr.devices[0].zones[z].state = ZoneState.OPEN \
                if arr.devices[0].zones[z].write_pointer else ZoneState.EMPTY


@pytest.mark.parametrize("tier", [CsdTier.INTERP, CsdTier.JIT, CsdTier.KERNEL])
def test_scheduler_degraded_offload_all_tiers(tier):
    data = int32_blocks(37, seed=12)           # partial tail chunk too
    dev = ZonedDevice(num_zones=2, zone_bytes=1024 * 1024, block_bytes=BLOCK)
    dev.zone_append(0, data)
    program = filter_count("int32", "gt", 0)
    want, _ = NvmCsd(dev).run_and_fetch(program, 0, tier=tier)
    arr = make_array(3, redundancy="xor", zone_kib=1024)
    arr.zone_append(0, data)
    arr.set_offline(0, device=1)
    got, stats = OffloadScheduler(arr).run_and_fetch(program, 0, tier=tier)
    assert int(want) == int(got)
    assert stats.degraded_reads > 0


def test_scheduler_member_death_mid_command_recovers_on_redundant_array():
    """Member dies while the fan-out is executing: redundant arrays redirect
    or reconstruct the affected chunks and still return the exact result."""
    data = int32_blocks(40, seed=13)
    expected = int((data > 0).sum())
    arr = make_array(2, redundancy="raid1", zone_kib=1024,
                     read_us_per_block=50.0)
    arr.zone_append(0, data)
    sched = OffloadScheduler(arr)
    program = filter_count("int32", "gt", 0)
    sched.nvm_cmd_bpf_run(program, 0)          # warm: pays JIT
    killer = threading.Timer(0.002, lambda: arr.set_offline(0, device=1))
    killer.start()
    try:
        got, _ = sched.run_and_fetch(program, 0)
    finally:
        killer.join()
    assert int(got) == expected


def test_scheduler_multi_tenant_stats_history():
    _, sched = oracle_pair(40)
    sched.register_tenant("analytics", weight=2)
    sched.submit(filter_count("int32", "gt", 0), 0, tenant="analytics")
    sched.submit(filter_count("int32", "lt", 0), 0)
    assert sched.drain() == 2
    assert len(sched.history) == 2
    assert {s.program for s in sched.history} == {
        "filter_count_gt", "filter_count_lt"}


# ------------------------- staged-pipeline stats + fault seams (ISSUE 10)

def test_offload_stats_report_per_stage_figures():
    """The pipelined path decomposes its wall time per STAGE (read wait /
    staging / combine) and counts batched dispatches — the per-worker
    fanout/overlap accounting is gone."""
    _, sched = oracle_pair(40)
    stats = sched.nvm_cmd_bpf_run(filter_count("int32", "gt", 0), 0)
    assert stats.n_dispatches >= 1
    assert stats.read_wait_seconds >= 0.0
    assert stats.stage_seconds >= 0.0
    assert stats.combine_seconds >= 0.0
    assert 0.0 <= stats.overlap_ratio <= 1.0
    # fanout names the array-wide batched dispatches, not a worker pool
    assert "dispatches" in stats.fanout
    assert f"{stats.n_chunks} chunks" in stats.fanout
    assert f"{stats.n_devices} devices" in stats.fanout


def test_array_statsview_dict_api_unchanged_by_pipeline():
    """Regression: the dict-shaped stats surfaces survive the staged
    refactor — same keys before/after an offload, mapping semantics on the
    device-level StatsView, integer values throughout."""
    arr = make_array(4)
    arr.zone_append(0, int32_blocks(40))
    keys_before = set(arr.stats)
    with OffloadScheduler(arr) as sched:
        sched.nvm_cmd_bpf_run(filter_count("int32", "gt", 0), 0)
    after = arr.stats
    assert set(after) == keys_before
    for key in ("blocks_read", "bytes_copied", "bytes_viewed",
                "degraded_reads", "read_errors"):
        assert key in after
    assert all(isinstance(v, (int, np.integer)) for v in after.values())
    view = arr.devices[0].stats
    assert view["blocks_read"] == dict(view)["blocks_read"]
    assert len(view) == len(list(view))


@pytest.mark.parametrize("mode,n", [("raid0", 4), ("raid1", 4), ("xor", 3)])
@pytest.mark.parametrize("tier", [CsdTier.JIT, CsdTier.KERNEL])
def test_batched_dispatch_bit_identical_across_tiers_and_modes(mode, n, tier):
    """The array-wide batched dispatch must return byte-identical answers
    to the single-device oracle at every redundancy mode and compiled tier,
    healthy AND with a member down (degraded chunks ride the same staged
    path) — raid0 has no redundancy, so only the healthy half applies."""
    data = int32_blocks(64, seed=21)
    dev = ZonedDevice(num_zones=2, zone_bytes=1024 * 1024, block_bytes=BLOCK)
    dev.zone_append(0, data)
    csd = NvmCsd(dev)
    arr = make_array(n, redundancy=mode, zone_kib=1024)
    arr.zone_append(0, data)
    sched = OffloadScheduler(arr)
    for program in (filter_count("int32", "gt", 0),
                    filter_sum("int32", "lt", 100)):
        want, _ = csd.run_and_fetch(program, 0, tier=tier)
        got, stats = sched.run_and_fetch(program, 0, tier=tier)
        assert np.array_equal(np.asarray(want), np.asarray(got))
        assert stats.batched_chunks > 0
        if mode != "raid0":
            arr.set_offline(0, device=0)
            degraded, d_stats = sched.run_and_fetch(program, 0, tier=tier)
            assert np.array_equal(np.asarray(want), np.asarray(degraded))
            assert d_stats.degraded_reads > 0
            for z in range(arr.num_zones):
                arr.devices[0].zones[z].state = ZoneState.OPEN \
                    if arr.devices[0].zones[z].write_pointer \
                    else ZoneState.EMPTY
    assert all(s.movement_saved_bytes > 0 for s in sched.history)


# ------------------------------------------- stage groups capped by bytes

ROW_BYTES = STRIPE * BLOCK          # one int32 chunk's pages in a group buffer
CAPPED_PROGRAMS = TERMINAL_PROGRAMS[:6]   # COUNT, SUM, MIN, MAX, HIST, SELECT


def _assert_same_answer(want, got):
    if isinstance(want, tuple):
        assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
        assert int(want[1]) == int(got[1])
    else:
        assert np.asarray(want).dtype == np.asarray(got).dtype
        assert np.array_equal(np.asarray(want), np.asarray(got))


def _spy_pieces(monkeypatch, arr) -> dict:
    """What the scheduler hands the device from here on: ``host`` holds
    ``(is a view of a member's zone bytes, rows)`` for every batched call's
    host pages, ``device`` a weak reference to every piece put to HBM,
    ``packed`` the rows of every group's packed runs."""
    from repro.core.vm import JittedProgram
    seen = {"host": [], "device": [], "packed": []}
    zones = [d._buf for d in arr.devices]
    real_call, real_put = JittedProgram.__call__, JittedProgram.put
    real_pack = OffloadScheduler._pack_on_land

    def note(pages):
        if isinstance(pages, np.ndarray) and pages.ndim == 3:
            seen["host"].append(
                (any(np.may_share_memory(pages, z) for z in zones),
                 pages.shape[0]))

    def call(self, pages):
        note(pages)
        return real_call(self, pages)

    def put(pages):
        note(pages)
        dev = real_put(pages)
        seen["device"].append(weakref.ref(dev))
        return dev

    def pack(grp, runs, chunk_pages, page_elems):
        seen["packed"].append(sum(len(r.items) for r in runs))
        real_pack(grp, runs, chunk_pages, page_elems)

    monkeypatch.setattr(JittedProgram, "__call__", call)
    monkeypatch.setattr(JittedProgram, "put", staticmethod(put))
    monkeypatch.setattr(OffloadScheduler, "_pack_on_land",
                        staticmethod(pack))
    return seen


def _traced_offload(sched, program, **kw):
    """Run one offload traced; returns its answer, stats, ``stage.dispatch``
    spans and the registry's delta over it."""
    trace.clear()
    before = registry().snapshot()
    with trace.tracing(True):
        got, stats = sched.run_and_fetch(program, 0, **kw)
    delta = registry().delta(before)
    dispatches = [e for e in trace.drain() if e["name"] == "stage.dispatch"]
    trace.clear()
    return got, stats, dispatches, delta


def _group_rows(dispatches) -> dict:
    """Rows dispatched per group, from the ``stage.dispatch`` tags."""
    rows: dict = {}
    for e in dispatches:
        rows[e["tags"]["group"]] = rows.get(e["tags"]["group"], 0) \
            + e["tags"]["rows"]
    return rows


def _is_pow2(n: int) -> bool:
    return n > 0 and not n & (n - 1)


@pytest.mark.parametrize("mode,n,dead", [("raid0", 4, None), ("xor", 4, 0),
                                         ("raid1", 4, None),
                                         ("raid0", 3, None), ("xor", 4, None)],
                         ids=["raid0", "xor-dead-member", "raid1", "raid0x3",
                              "xor"])
@pytest.mark.parametrize("program", CAPPED_PROGRAMS,
                         ids=[p.name for p in CAPPED_PROGRAMS])
def test_stage_groups_capped_by_bytes(monkeypatch, program, mode, n, dead):
    """An extent whose prefetch-depth groups would pass the group budget
    streams through budget-sized groups with a ragged tail: same answers
    as the oracle, no group and no dispatch over the budget, power-of-two
    pieces, the clamp counted once. A member's share of a group that is one
    run of two or more chunks (raid0) goes to the device as a view of its
    zone bytes; every other run (raid1's round-robin replicas, xor's runs
    cut by parity rows) is packed into the group's one part, so a group
    costs at most ``n × log2(64)`` dispatches whatever the layout."""
    budget = 65 * ROW_BYTES                    # the widest power of two: 64
    monkeypatch.setattr(scheduler_mod, "_STAGE_GROUP_BYTES", budget)
    data = int32_blocks(4 * 200 + 2, seed=31)  # 200 full chunks and a tail
    arr = make_array(n, redundancy=mode, num_zones=1, zone_kib=4096)
    arr.zone_append(0, data)
    if dead is not None:
        arr.set_offline(0, device=dead)
    seen = _spy_pieces(monkeypatch, arr)
    got, stats, dispatches, delta = _traced_offload(
        OffloadScheduler(arr), program)
    _assert_same_answer(run_oracle(program, data), got)
    m = stats.batched_chunks
    per_group = _group_rows(dispatches)
    assert sorted(per_group) == list(range(-(-m // 64))) and len(per_group) >= 2
    assert sum(per_group.values()) == m and max(per_group.values()) == 64
    assert m % 64                              # a ragged tail group
    assert all(_is_pow2(e["tags"]["rows"]) for e in dispatches)
    assert all(e["tags"]["bytes"] <= budget for e in dispatches)
    assert stats.n_dispatches == len(dispatches) == len(seen["host"])
    assert delta["sched.stage.groups_capped"] == 1
    direct = sum(r for view, r in seen["host"] if view)
    packed = sum(r for view, r in seen["host"] if not view)
    assert direct == delta.get("sched.stage.rows_direct", 0)
    assert packed == delta.get("sched.stage.rows_packed", 0)
    assert direct + packed == m
    # nothing is copied but the packed runs, one part a group at most
    assert packed == sum(seen["packed"])
    assert len(seen["packed"]) <= len(per_group)
    pieces: dict = {}
    for e in dispatches:
        pieces[e["tags"]["group"]] = pieces.get(e["tags"]["group"], 0) + 1
    assert max(pieces.values()) <= n * math.log2(64)
    if mode == "raid0":                        # a share is one run of 2+
        assert packed == 0 and seen["packed"] == []
    elif mode == "raid1":                      # every run is one chunk
        assert direct == 0 and packed == m
    else:                                      # parity cuts every share
        assert packed > 0
    assert (stats.degraded_reads > 0) == (dead is not None)


@pytest.mark.parametrize("n_blocks,depth", [(40, 2), (64, 2), (78, 1),
                                            (78, 3), (200, 2)])
def test_stage_groups_under_budget_keep_prefetch_depth_rule(n_blocks, depth):
    """Under the group budget the width is the prefetch-depth rule's:
    ``prefetch_depth`` groups, bucketed up to a power of two, at least 2;
    each group goes out in power-of-two pieces and no pad rows."""
    data = int32_blocks(n_blocks, seed=32)
    arr = make_array(4, zone_kib=1024)
    arr.zone_append(0, data)
    program = filter_sum("int32", "lt", 100)
    got, stats, dispatches, delta = _traced_offload(
        OffloadScheduler(arr, prefetch_depth=depth), program)
    _assert_same_answer(run_oracle(program, data), got)
    m = n_blocks // STRIPE
    m_b = max(2, 1 << math.ceil(math.log2(-(-m // min(depth, m)))))
    assert m_b * ROW_BYTES <= scheduler_mod._STAGE_GROUP_BYTES
    assert stats.batched_chunks == m
    per_group = _group_rows(dispatches)
    assert per_group == {g: min(m_b, m - g * m_b)
                         for g in range(-(-m // m_b))}
    assert all(_is_pow2(e["tags"]["rows"]) and e["tags"]["rows"] <= m_b
               for e in dispatches)
    assert stats.n_dispatches == len(dispatches)
    assert delta.get("sched.stage.groups_capped", 0) == 0


@pytest.mark.parametrize("capped", [False, True], ids=["depth", "capped"])
def test_stage_buffer_released_once_its_group_landed(monkeypatch, capped):
    """By the combine rendezvous every piece has landed its partial, and
    nothing of the offload still holds a piece's device array."""
    if capped:
        monkeypatch.setattr(scheduler_mod, "_STAGE_GROUP_BYTES",
                            9 * ROW_BYTES)
    arr = make_array(4, zone_kib=1024)
    arr.zone_append(0, int32_blocks(4 * 19, seed=33))
    sched = OffloadScheduler(arr)
    program = filter_count("int32", "gt", 0)
    sched.nvm_cmd_bpf_run(program, 0)          # compile outside the check
    seen = _spy_pieces(monkeypatch, arr)
    alive_at_rendezvous = []
    real_result = scheduler_mod._StagedCombiner.result

    def result(self):
        self._done.wait()
        deadline = time.monotonic() + 5.0
        while (any(ref() is not None for ref in seen["device"])
               and time.monotonic() < deadline):
            gc.collect()
            time.sleep(0.01)
        alive_at_rendezvous.append(
            sum(ref() is not None for ref in seen["device"]))
        return real_result(self)

    monkeypatch.setattr(scheduler_mod._StagedCombiner, "result", result)
    with trace.tracing(True):
        stats = sched.nvm_cmd_bpf_run(program, 0)
    trace.clear()
    # groups of 16 and 3 (depth), or of 8, 8 and 3 (capped): runs of 4 or
    # 2 chunks a member, then the tail's three one-chunk shares packed
    assert stats.n_dispatches == (4 + 4 + 2 if capped else 4 + 2)
    assert len(seen["device"]) == stats.n_dispatches
    assert alive_at_rendezvous == [0]


@pytest.mark.parametrize("depth", [1, 2])
def test_outstanding_groups_bounded_by_prefetch_depth(monkeypatch, depth):
    """A group's first piece is dispatched only once every piece of all but
    ``prefetch_depth - 1`` of the groups before it has materialized its
    partial, so HBM holds at most that many groups' inputs and the pool at
    most that many groups' lands."""
    monkeypatch.setattr(scheduler_mod, "_STAGE_GROUP_BYTES", 9 * ROW_BYTES)
    data = int32_blocks(4 * 40, seed=34)       # 5 groups of 8: 4 runs of 2
    arr = make_array(4, zone_kib=1024)
    arr.zone_append(0, data)
    sched = OffloadScheduler(arr, prefetch_depth=depth)
    program = filter_count("int32", "gt", 0)
    sched.nvm_cmd_bpf_run(program, 0)          # compile outside the check
    trace.clear()
    with trace.tracing(True):
        got, _ = sched.run_and_fetch(program, 0)
    events = trace.drain()
    trace.clear()
    assert int(got) == int(run_oracle(program, data))
    starts: dict = {}
    landed: dict = {}
    for e in events:
        g = e["tags"].get("group")
        if e["name"] == "stage.dispatch":
            starts[g] = min(starts.get(g, e["ts"]), e["ts"])
        elif e["name"] == "stage.materialize":
            landed.setdefault(g, []).append(e["ts"] + e["dur"])
    assert sorted(starts) == sorted(landed) == list(range(5))
    assert all(len(ends) == 4 for ends in landed.values())
    for g, t in starts.items():
        done = sum(max(ends) <= t for ends in landed.values())
        assert done >= g - depth + 1


def test_gather_pool_runs_ahead_jobs_before_queued_ones():
    from repro.array.striping import _GatherPool
    pool = _GatherPool(max_workers=1)
    gate, order = threading.Event(), []
    try:
        pool.submit(gate.wait)                 # holds the only worker
        for i in range(3):
            pool.submit(lambda i=i: order.append(i))
        pool.submit(lambda: order.append("ahead"), ahead=True)
        gate.set()
        deadline = time.monotonic() + 5.0
        while len(order) < 4 and time.monotonic() < deadline:
            time.sleep(0.005)
    finally:
        gate.set()
        pool.shutdown()
    assert order == ["ahead", 0, 1, 2]
