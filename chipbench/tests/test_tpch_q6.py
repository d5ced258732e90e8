"""The TPC-H Q6 cell: its ``lineitem`` generator makes the same rows from a
seed whatever order its chunks are made in, keeps every column in its
TPC-H §4.2.3 range, and at SF 1 with the validation parameters gives a
revenue within 3% of the spec's published SF 1 answer; a whole run of the
cell at a small zone on the CPU reads correct, and with the timed path
broken, not correct.
"""
import numpy as np
import pytest

import deploy
import named
import run
from test_faults import _run, alter_answer, drop_half, truncate_extent

CELL = "tpch-sf1.q6"
SPEC = {"zone": 0, "dtype": "int32", "dist": "lineitem", "rows": 6001215}
ZONE_BYTES = 1129316352
SEED = 2**33 + 1601
# TPC-H spec §2.4.6: the validation query's answer at SF 1, 123,141,078.23,
# in the records' 0.0001 currency units (cents × hundredths)
PUBLISHED_SF1 = 1_231_410_782_300
VALIDATION = {"kind": "q6", "date": "1994-01-01", "discount": 6,
              "quantity": 24, "name": "q6_1994"}

lineitem = named.load("zones", "lineitem")
q6 = named.load("programs", "q6")


def _days(date: str) -> int:
    return int((np.datetime64(date, "D")
                - np.datetime64("1970-01-01", "D")).astype(int))


@pytest.fixture(scope="module")
def sf1():
    """The whole SF 1 table, as the cell's deployment generates it."""
    data = deploy.zone_data(SPEC, SEED, ZONE_BYTES, 4096)
    return data.reshape(-1, lineitem.STRIDE)


def test_same_seed_same_rows_other_seed_other_rows():
    a = deploy.zone_data(SPEC, SEED, 1 << 20, 4096)
    assert np.array_equal(a, deploy.zone_data(SPEC, SEED, 1 << 20, 4096))
    assert not np.array_equal(a, deploy.zone_data(SPEC, SEED + 1, 1 << 20,
                                                  4096))


def test_chunks_do_not_depend_on_the_order_they_are_made_in():
    n = 3 * deploy._CHUNK + 32 * 1000        # three whole chunks and a part
    spec = dict(SPEC, rows=n // lineitem.STRIDE)
    whole = deploy.zone_data(spec, SEED, n * 4, 4096)
    kids = deploy.seed_words(SEED, 1, 0).spawn(4)
    for i in (3, 1, 0, 2):
        start = i * deploy._CHUNK
        out = np.empty(min(deploy._CHUNK, n - start), np.int32)
        lineitem.fill(spec, np.random.Generator(np.random.PCG64(kids[i])),
                      out, start)
        assert np.array_equal(out, whole[start:start + out.size]), i


def test_elements_hold_whole_records_up_to_the_capacity():
    assert lineitem.elements(SPEC, ZONE_BYTES // 4) == 6001215 * 32
    assert lineitem.elements(SPEC, (1 << 20) // 4) == (1 << 20) // 4
    assert lineitem.elements(SPEC, 100) == 96


def test_every_column_stays_in_its_range(sf1):
    rows = sf1[:SPEC["rows"]]
    assert not sf1[SPEC["rows"]:].any()          # the zero-padded record

    def within(word, lo, hi):
        col = rows[:, word]
        assert lo <= col.min() and col.max() <= hi, (word, col.min(),
                                                     col.max())
    within(0, 1, 4 * 1_500_304)    # sparse keys of 1,500,304 orders
    within(1, 1, 200_000)
    within(2, 1, 10_000)
    within(3, 1, 7)
    within(4, 1, 50)
    within(6, 0, 10)
    within(7, 0, 8)
    assert np.array_equal(rows[:, 5], rows[:, 4]
                          * lineitem.retailprice(rows[:, 1]))
    first, last = _days("1992-01-01"), _days("1998-08-02")
    within(8, first + 1, last + 121)
    within(9, first + 30, last + 90)
    assert ((rows[:, 10] - rows[:, 8] >= 1)
            & (rows[:, 10] - rows[:, 8] <= 30)).all()
    flags = rows[:, 11]
    assert set(np.unique(flags & 0xFF)) <= {ord("R"), ord("A"), ord("N")}
    assert set(np.unique(flags >> 8)) <= {ord("O"), ord("F")}


def test_validation_revenue_at_sf1_is_near_the_published_answer(sf1):
    got = int(q6.answer(VALIDATION, sf1))
    assert abs(got / PUBLISHED_SF1 - 1) < 0.03, got
    assert q6.control(VALIDATION, sf1) != got


def test_sound_run_is_correct():
    res = _run(CELL)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res["checks"]) == ["revenue_gap", "extent_wrong",
                                   "unanswered"]
    assert set(res["metrics"]) == {"scan_gib_s.csd", "setup_s"}


@pytest.mark.parametrize("fault", [alter_answer, drop_half, truncate_extent],
                         ids=lambda f: f.__name__)
def test_broken_path_is_not_correct(fault, monkeypatch):
    res = _run(CELL, mutate=fault(monkeypatch))
    assert res["correct"] is False, res["checks"]


def _span(name, dur, **tags):
    return {"type": "span", "name": name, "ts": 0.0, "dur": dur,
            "track": None, "tid": 1, "thread": "t", "tags": tags,
            "id": 1, "parent": None}


def _ctx(spans):
    return run.Context([], 0.0, 0.0, 4096, spans=spans, reg={})


def test_q6_span_readers():
    spans = [_span("tier.put", 0.07), _span("tier.put", 0.09),
             _span("tier.run", 0.38, stride=32, columns=4),
             _span("tier.run", 0.40, stride=32, columns=4),
             _span("tier.run", 0.002)]      # a program without FIELD
    assert run.load_metric("run_ms.q6")(_ctx(spans)) == pytest.approx(390.0)
    assert run.load_metric("put_ms.q6")(_ctx(spans)) == pytest.approx(80.0)
    # the parent's spans carry no ``columns``: nothing to read, no raise
    assert run.load_metric("run_ms.q6")(_ctx(spans[-1:])) is None
    assert run.load_metric("put_ms.q6")(_ctx([])) is None
