"""Record programs: predicates over several columns of one record and the
product of two columns (``LOAD``, ``MUL_FIELD`` after a ``FIELD``), as TPC-H
Q6 needs them. Every tier, the array scheduler and ``NvmCsd``'s default path
give exactly what a plain-numpy Q6 gives, boundary rows included; the
verifier refuses a record op without its FIELD or outside its record; and
while tracing, Q6's offloads carry their record tags and count their
records."""
import jax
import numpy as np
import pytest

from repro.array import OffloadScheduler, StripedZoneArray
from repro.core import (
    CsdTier,
    Instruction,
    NvmCsd,
    OpCode,
    Program,
    VerifyError,
    field_reduce,
    filter_count,
    interpret_program,
    jit_program,
    run_oracle,
    verify_program,
)
from repro.core.csd import resolve_tier
from repro.core.programs import select_records, tpch_q6
from repro.core import vm
from repro.core.vm import jit_program_batched
from repro.kernels.zone_filter.ops import kernelizable
from repro.telemetry import trace
from repro.telemetry.metrics import registry
from repro.zns import ZonedDevice

BLOCK = 4096
STRIDE = 32                  # 128-B lineitem records, 32 a page
QTY, PRICE, DISC, SHIP = 4, 5, 6, 8
ROWS = 4096
SEEDS = [0, 1, 2**33 + 16]
DAY_1994, DAY_1995 = 8766, 9131          # days since 1970-01-01
PARAMS = {"date_lo": DAY_1994, "date_hi": DAY_1995, "disc_lo": 5,
          "disc_hi": 7, "qty_lt": 24}      # the validation set: 0.06, 24


def q6_program(**params) -> Program:
    return tpch_q6(STRIDE, shipdate=SHIP, discount=DISC, quantity=QTY,
                   extendedprice=PRICE, **(params or PARAMS))


def q6_numpy(rows: np.ndarray, date_lo, date_hi, disc_lo, disc_hi,
             qty_lt) -> int:
    """TPC-H §2.4.6 in the records' integer units."""
    rec = rows.reshape(-1, STRIDE)
    keep = ((rec[:, SHIP] >= date_lo) & (rec[:, SHIP] < date_hi)
            & (rec[:, DISC] >= disc_lo) & (rec[:, DISC] <= disc_hi)
            & (rec[:, QTY] < qty_lt))
    return int((rec[keep, PRICE].astype(np.int64)
                * rec[keep, DISC].astype(np.int64)).sum())


# each hand-placed row passes every predicate but the one it sits on, and
# sits on one side of that one's bound: (column, value, counted)
BOUNDARY = [
    (SHIP, DAY_1994, True), (SHIP, DAY_1994 - 1, False),
    (SHIP, DAY_1995 - 1, True), (SHIP, DAY_1995, False),
    (DISC, 4, False), (DISC, 5, True), (DISC, 7, True), (DISC, 8, False),
    (QTY, 23, True), (QTY, 24, False),
]


def lineitem_rows(seed: int, n: int = ROWS) -> np.ndarray:
    """Seeded records: Q6's columns in their TPC-H ranges, the other words
    any int32; the boundary rows at the start, the last at the very end."""
    g = np.random.default_rng(seed)
    rec = g.integers(-2**31, 2**31, (n, STRIDE), dtype=np.int32)
    part = g.integers(1, 200_000, n, endpoint=True)
    rec[:, QTY] = g.integers(1, 50, n, endpoint=True)
    rec[:, PRICE] = rec[:, QTY] * (90000 + (part // 10) % 20001
                                   + 100 * (part % 1000))
    rec[:, DISC] = g.integers(0, 10, n, endpoint=True)
    rec[:, SHIP] = g.integers(8036, 10561, n, endpoint=True)
    for i, (col, value, _) in enumerate(BOUNDARY):
        at = n - 1 if i == len(BOUNDARY) - 1 else i
        rec[at, [SHIP, DISC, QTY]] = (DAY_1994 + 100, 6, 10)
        rec[at, col] = value
    return rec


def test_boundary_rows_count_as_placed():
    rec = lineitem_rows(0)
    rows = [rec[i] for i in range(len(BOUNDARY) - 1)] + [rec[-1]]
    for row, (col, value, counted) in zip(rows, BOUNDARY):
        got = q6_numpy(row, **PARAMS)
        assert (got == int(row[PRICE]) * int(row[DISC])) == counted, (col,
                                                                      value)
        assert run_oracle(q6_program(), row) == got


def _pages(rec: np.ndarray) -> np.ndarray:
    return rec.reshape(-1, BLOCK // 4)


TIERS = {
    "oracle": lambda p, pages: run_oracle(p, pages),
    "interp": lambda p, pages: interpret_program(
        p, lambda i: pages[i], *pages.shape).value,
    "jit": lambda p, pages: jit_program(p, *pages.shape)(pages),
    "jit_batched": lambda p, pages: np.asarray(jit_program_batched(
        p, 4, pages.shape[0] // 4, pages.shape[1])(
            pages.reshape(4, -1, pages.shape[1]))).sum(),
}


@pytest.mark.parametrize("tier", sorted(TIERS))
@pytest.mark.parametrize("seed", SEEDS)
def test_tiers_agree_with_numpy_q6(seed, tier):
    rec = lineitem_rows(seed)
    params = dict(PARAMS) if seed != SEEDS[-1] else {
        "date_lo": 9862, "date_hi": 10227, "disc_lo": 1, "disc_hi": 3,
        "qty_lt": 25}                  # 1997, 0.02, 25
    want = q6_numpy(rec, **params)
    got = TIERS[tier](q6_program(**params), _pages(rec))
    assert np.asarray(got).dtype == np.int64
    assert int(got) == want
    assert want > 0


STEP = 4                     # pages a JIT tile, with the step constant small
PAGE_ELEMS = BLOCK // 4
# about 59 of a tile's 128 records have a quantity under 24: a buffer of
# 100 fills in the second tile
TILED_PROGRAMS = [q6_program(),
                  select_records("int32", STRIDE, QTY, "lt", 24, capacity=100)]


@pytest.mark.parametrize("batched", [False, True], ids=["single", "batched"])
@pytest.mark.parametrize("n_pages", [1, STEP - 1, STEP, STEP + 1, 2 * STEP + 3])
@pytest.mark.parametrize("program", TILED_PROGRAMS, ids=lambda p: p.name)
def test_tiled_jit_scan_of_records_matches_oracle(monkeypatch, program,
                                                  n_pages, batched):
    """Records read from tiles of ``STEP`` pages, a tail step and an extent
    under one tile give the page-by-page answer, per chunk when batched."""
    rows = n_pages * PAGE_ELEMS // STRIDE
    extents = [_pages(lineitem_rows(s, rows)) for s in range(2 if batched
                                                              else 1)]
    monkeypatch.setattr(vm, "_SCAN_STEP_BYTES",
                        STEP * BLOCK * len(extents))
    if batched:
        jp = jit_program_batched(program, 2, n_pages, PAGE_ELEMS)
        out = jp(np.stack(extents))
        got = [jax.tree.map(lambda a, c=c: a[c], out) for c in range(2)]
    else:
        jp = jit_program(program, n_pages, PAGE_ELEMS)
        got = [jp(extents[0])]
    assert jp.steps == -(-n_pages // min(STEP, n_pages))
    for pages, answer in zip(extents, got):
        want = run_oracle(program, pages)
        if program.terminal.op == OpCode.SELECT_REC:
            np.testing.assert_array_equal(np.asarray(answer[0]), want[0])
            assert int(answer[1]) == int(want[1])
        else:
            assert int(answer) == int(want) == q6_numpy(pages, **PARAMS)


def test_mul_field_wraps_like_mul():
    rec = np.zeros((32, 4), np.int32)
    rec[:, 1] = 2**30 + np.arange(32)
    rec[:, 2] = 4
    prog = Program("int32", (Instruction(OpCode.FIELD, (4, 1)),
                             Instruction(OpCode.MUL_FIELD, 2),
                             Instruction(OpCode.RED_SUM)))
    want = int((rec[:, 1] * rec[:, 2]).astype(np.int64).sum())  # wrapped
    assert int(run_oracle(prog, rec)) == want
    pages = rec.reshape(1, -1)
    assert int(interpret_program(prog, lambda i: pages[i], 1, 128).value) \
        == want
    assert int(jit_program(prog, 1, 128)(pages)) == want


BAD = {
    "load_without_field": Program("int32", (
        Instruction(OpCode.LOAD, 1), Instruction(OpCode.CMP_GT, 0),
        Instruction(OpCode.RED_COUNT))),
    "mul_field_without_field": Program("int32", (
        Instruction(OpCode.MUL_FIELD, 1), Instruction(OpCode.RED_SUM))),
    "load_past_stride": Program("int32", (
        Instruction(OpCode.FIELD, (8, 0)), Instruction(OpCode.LOAD, 8),
        Instruction(OpCode.RED_SUM))),
    "mul_field_past_stride": Program("int32", (
        Instruction(OpCode.FIELD, (8, 0)), Instruction(OpCode.MUL_FIELD, 9),
        Instruction(OpCode.RED_SUM))),
    "negative_column": Program("int32", (
        Instruction(OpCode.FIELD, (8, 0)), Instruction(OpCode.LOAD, -1),
        Instruction(OpCode.RED_SUM))),
    "load_before_field": Program("int32", (
        Instruction(OpCode.LOAD, 1), Instruction(OpCode.FIELD, (8, 0)),
        Instruction(OpCode.RED_SUM))),
}


@pytest.mark.parametrize("name", sorted(BAD))
def test_verifier_rejects(name):
    with pytest.raises(VerifyError):
        verify_program(BAD[name], page_elems=1024, n_pages=4)


def test_verifier_admits_q6_with_the_linear_bound():
    prog = q6_program()
    assert verify_program(prog, page_elems=1024, n_pages=128) \
        == prog.n_insns * 128


def test_columns_a_program_reads():
    assert q6_program().stride == STRIDE
    assert q6_program().columns == {SHIP, DISC, QTY, PRICE}
    assert field_reduce("int32", 8, 2, "sum").columns == {2}
    assert select_records("int32", 8, 0, "gt", 0, 4).columns == set(range(8))
    assert filter_count("int32", "gt", 0).stride is None
    assert filter_count("int32", "gt", 0).columns == set()


def test_kernel_tier_resolves_q6_to_jit():
    assert not kernelizable(q6_program())
    assert resolve_tier(CsdTier.KERNEL, q6_program()) == CsdTier.JIT


def _device(rec: np.ndarray) -> ZonedDevice:
    dev = ZonedDevice(num_zones=1, zone_bytes=1 << 20, block_bytes=BLOCK)
    dev.zone_append(0, rec.reshape(-1))
    return dev


@pytest.mark.parametrize("default_tier", [CsdTier.JIT, CsdTier.KERNEL])
def test_nvmcsd_default_path_runs_q6(default_tier):
    rec = lineitem_rows(SEEDS[1])
    csd = NvmCsd(_device(rec), default_tier=default_tier)
    got, stats = csd.run_and_fetch(q6_program(), 0)
    assert int(got) == q6_numpy(rec, **PARAMS)
    assert stats.tier == CsdTier.JIT
    assert stats.bytes_read == rec.nbytes


@pytest.mark.parametrize("tier", [CsdTier.INTERP, CsdTier.JIT])
def test_q6_through_the_array_equals_numpy(tier):
    # 4150 records: 130 blocks over four members in stripes of 4, so both
    # the batched chunks and a short tail chunk carry int64 partials
    rec = lineitem_rows(SEEDS[2], 4150)
    flat = np.zeros(130 * BLOCK // 4, np.int32)
    flat[:rec.size] = rec.reshape(-1)
    devs = [ZonedDevice(num_zones=1, zone_bytes=256 * 1024,
                        block_bytes=BLOCK) for _ in range(4)]
    arr = StripedZoneArray(devs, stripe_blocks=4, redundancy="raid0")
    arr.zone_append(0, flat)
    with OffloadScheduler(arr) as sched:
        got, stats = sched.run_and_fetch(q6_program(), 0, tier=tier)
    assert np.asarray(got).dtype == np.int64
    assert int(got) == q6_numpy(flat, **PARAMS)
    assert stats.n_chunks == 33


@pytest.fixture
def traced():
    trace.set_enabled(False)
    trace.clear()
    yield
    trace.set_enabled(False)
    trace.clear()


def test_tracing_tags_q6_runs_and_counts_records(traced):
    rec = lineitem_rows(SEEDS[0])
    csd = NvmCsd(_device(rec))
    prog = q6_program()
    csd.run_and_fetch(prog, 0)                       # compiled outside
    csd.run_and_fetch(filter_count("int32", "gt", 0), 0)
    before = registry().snapshot()
    with trace.tracing(True):
        csd.run_and_fetch(prog, 0)
        csd.run_and_fetch(filter_count("int32", "gt", 0), 0)
    delta = registry().delta(before)
    events = trace.drain()
    runs = [e["tags"] for e in events if e["name"] == "tier.run"]
    assert runs[0]["stride"] == STRIDE and runs[0]["columns"] == 4
    assert "stride" not in runs[1] and "columns" not in runs[1]
    assert [e["tags"]["program"] for e in events
            if e["name"] == "csd.verify"] == [prog.name, "filter_count_gt"]
    assert delta["csd.records"] == ROWS


def test_records_are_not_counted_while_tracing_is_off(traced):
    csd = NvmCsd(_device(lineitem_rows(SEEDS[0])))
    before = registry().snapshot()
    csd.run_and_fetch(q6_program(), 0)
    assert registry().delta(before).get("csd.records", 0) == 0
    assert trace.drain() == []
