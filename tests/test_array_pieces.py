"""The array scan's staged groups go to the device part by part: a member's
share that is one long run as the view its read returned, every shorter
run packed into the group's one part, each part cut into power-of-two
pieces. Rows are member-major, so these tests hold the
answers to the plain reference, SELECT's logical order included, over
every layout that cuts runs differently, and bound the compiles the piece
widths cost."""
import math
import sys
import threading

import numpy as np
import pytest

from repro.array import OffloadScheduler, StripedZoneArray
from repro.array.scheduler import _pieces
from repro.core import CsdTier
from repro.core.cache import CompiledProgramCache
from repro.core.programs import (
    Instruction,
    OpCode,
    Program,
    filter_count,
    filter_select,
    filter_sum,
    histogram,
)
from repro.core.vm import run_oracle
from repro.telemetry.metrics import registry
from repro.zns import ZonedDevice

BLOCK = 4096
STRIPE = 4
ELEMS = BLOCK // 4            # int32 elements a block

PROGRAMS = [
    filter_count("int32", "gt", 0),
    filter_sum("int32", "lt", 100),
    Program("int32", (Instruction(OpCode.CMP_LT, 500),
                      Instruction(OpCode.RED_MIN)), name="filter_min_lt"),
    Program("int32", (Instruction(OpCode.CMP_GT, -500),
                      Instruction(OpCode.RED_MAX)), name="filter_max_gt"),
    histogram("int32", -1000, 1000, 32),
    filter_select("int32", "gt", 900, 64),
]

# (members, redundancy, dead member)
LAYOUTS = {
    "raid0x4": (4, "raid0", None),
    "raid0x3": (3, "raid0", None),
    "raid0x1": (1, "raid0", None),
    "raid1x4": (4, "raid1", None),
    "xor4": (4, "xor", None),
    "xor4-dead": (4, "xor", 1),
}

# An unaligned start (a 1-block head chunk), 43 full chunks, a 2-block tail:
# under the prefetch rule the groups are 32 and 11 chunks, so raid0x4's
# tail group splits into runs of 3, 3, 3 and 2 rows.
BLOCK_OFF = 3
N_BLOCKS = 1 + 43 * STRIPE + 2

_CACHE = CompiledProgramCache()


def _array(members, redundancy, n_blocks, seed):
    devs = [ZonedDevice(num_zones=1, zone_bytes=1024 * 1024,
                        block_bytes=BLOCK) for _ in range(members)]
    arr = StripedZoneArray(devs, stripe_blocks=STRIPE, redundancy=redundancy)
    rng = np.random.default_rng(seed)
    data = rng.integers(-1000, 1000, n_blocks * ELEMS, dtype=np.int32)
    arr.zone_append(0, data)
    return arr, data


def _same(want, got):
    if isinstance(want, tuple):
        assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
        assert int(want[1]) == int(got[1])
    else:
        assert np.asarray(want).dtype == np.asarray(got).dtype
        assert np.array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("n,widths", [(1, [1]), (2, [2]), (3, [2, 1]),
                                      (336, [256, 64, 16]), (512, [512]),
                                      (511, [256, 128, 64, 32, 16, 8, 4, 2, 1])])
def test_pieces_are_the_set_bits_widest_first(n, widths):
    assert _pieces(n) == widths


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("tier", [CsdTier.JIT, CsdTier.KERNEL])
@pytest.mark.parametrize("program", PROGRAMS, ids=[p.name for p in PROGRAMS])
def test_pieces_answer_as_the_reference(program, tier, layout):
    """Bit-identical to ``run_oracle`` over the extent, whatever lengths the
    layout cuts its runs into: long raid0 runs, a 1-member array's single
    run, raid1's packed single-chunk runs, xor's packed runs cut by parity,
    and a dead xor member's chunks rebuilt beside the batch."""
    members, redundancy, dead = LAYOUTS[layout]
    arr, data = _array(members, redundancy, BLOCK_OFF + N_BLOCKS + 3, seed=41)
    if dead is not None:
        arr.set_offline(0, device=dead)
    before = registry().snapshot()
    got, stats = OffloadScheduler(arr, cache=_CACHE).run_and_fetch(
        program, 0, block_off=BLOCK_OFF, n_blocks=N_BLOCKS, tier=tier)
    delta = registry().delta(before)
    want = run_oracle(program, data[BLOCK_OFF * ELEMS:
                                    (BLOCK_OFF + N_BLOCKS) * ELEMS])
    _same(want, got)
    assert stats.batched_chunks == (delta.get("sched.stage.rows_direct", 0)
                                    + delta.get("sched.stage.rows_packed", 0))
    assert stats.batched_chunks >= 30
    if redundancy == "raid0":
        assert delta.get("sched.stage.rows_packed", 0) == 0
    if redundancy == "raid1":
        assert delta.get("sched.stage.rows_direct", 0) == 0
    if redundancy == "xor":                   # parity rows cut every share
        assert delta.get("sched.stage.rows_packed", 0) > 0
    assert (stats.degraded_reads > 0) == (dead is not None)


@pytest.mark.parametrize("tier,members", [(CsdTier.JIT, 1), (CsdTier.JIT, 3),
                                          (CsdTier.JIT, 4),
                                          (CsdTier.KERNEL, 4)])
def test_piece_widths_keep_compiles_log_bounded(tier, members):
    """Over extents of 2..64 chunks, every extent size, the cache holds at
    most ceil(log2(64)) batched executables for the program: one a
    power-of-two width, never one an extent size."""
    arr, data = _array(members, "raid0", 64 * STRIPE, seed=42)
    program = filter_count("int32", "gt", 0)
    sched = OffloadScheduler(arr, cache=CompiledProgramCache())
    for m in range(2, 65):
        got, _ = sched.run_and_fetch(program, 0, n_blocks=m * STRIPE,
                                     tier=tier)
        assert int(got) == int(run_oracle(program,
                                          data[:m * STRIPE * ELEMS]))
    batched = [k for k in sched.cache._entries
               if k[0] in ("jit_batched", "kernel_batched")
               and k[1] == program]
    assert 0 < len(batched) <= math.ceil(math.log2(64))
    assert all(k[2] & (k[2] - 1) == 0 for k in batched)


def test_group_slot_freed_once_by_its_last_piece_under_thread_switching(
        monkeypatch):
    """Every piece's land() counts its group down on a pool thread; the
    last frees the group's slot. With one slot, six groups of 6-8 pieces
    and the interpreter switching threads as often as it can, a lost or
    doubled count would hang the dispatcher or let a group's pieces start
    before the previous group landed: neither happens, five times over."""
    from repro.array import scheduler as scheduler_mod
    from repro.telemetry import trace
    monkeypatch.setattr(scheduler_mod, "_STAGE_GROUP_BYTES",
                        33 * STRIPE * BLOCK)           # groups of 32
    arr, data = _array(3, "raid0", 190 * STRIPE, seed=43)
    program = filter_count("int32", "gt", 0)
    sched = OffloadScheduler(arr, prefetch_depth=1, cache=_CACHE)
    sched.run_and_fetch(program, 0)                     # compile first
    want = int(run_oracle(program, data))
    results, events = [], []

    def offloads():
        for _ in range(5):
            trace.clear()
            with trace.tracing(True):
                got, _ = sched.run_and_fetch(program, 0)
            results.append(int(got))
            events.append(trace.drain())

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        t = threading.Thread(target=offloads, daemon=True)
        t.start()
        t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
        trace.clear()
    assert not t.is_alive()
    assert results == [want] * 5
    for evs in events:
        first, last = {}, {}
        for e in evs:
            g = e["tags"].get("group")
            if e["name"] == "stage.dispatch":
                first[g] = min(first.get(g, e["ts"]), e["ts"])
            elif e["name"] == "stage.materialize":
                last[g] = max(last.get(g, 0.0), e["ts"] + e["dur"])
        assert sorted(first) == sorted(last) == list(range(6))
        for g in range(1, 6):
            assert last[g - 1] <= first[g]
