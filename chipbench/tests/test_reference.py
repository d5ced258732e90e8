"""Every program kind's reference agrees with the program's own oracle
(``repro.core.run_oracle``) where the oracle implements the program: the
``filter`` kind for every program the mixes send and every terminal it
implements, and the test-only ``field`` kind (FIELD, CMP, SUM)."""
import json

import numpy as np
import pytest

from conftest import BENCH, NEW_CELL

import loadgen
import named
from repro.core import run_oracle

MIXES = sorted((BENCH / "traffic").glob("*.json"))
# every terminal the filter kind implements, so that a mix added as data
# alone is held to a tested reference
TERMINALS = {
    "min_gt_half": {"dtype": "int32", "filter": ["gt", 1073741823],
                    "reduce": "min"},
    "max_lt_half": {"dtype": "int32", "filter": ["lt", 1073741823],
                    "reduce": "max"},
    "select_top": {"dtype": "int32", "filter": ["gt", 2147467647],
                   "reduce": "select", "capacity": 1024},
    "fsum_gt0": {"dtype": "float32", "filter": ["gt", 0.0], "reduce": "sum"},
    "fmax": {"dtype": "float32", "filter": None, "reduce": "max"},
}
PROGRAMS = sorted({(n, json.dumps(s, sort_keys=True))
                   for p in MIXES
                   for n, s in json.loads(p.read_text())["programs"].items()
                   if s.get("kind", "filter") == "filter"}
                  | {(n, json.dumps(s, sort_keys=True))
                     for n, s in TERMINALS.items()})
FIELD = json.loads((NEW_CELL / "traffic" / "field-scan.json").read_text())


def _data(dtype: str, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if dtype == "int32":
        return rng.integers(0, 2**31 - 1, n, dtype=np.int32)
    return rng.standard_normal(n, dtype=np.float32) * np.float32(100)


def _same(got, want) -> bool:
    if isinstance(want, tuple):
        return int(got[1]) == int(want[1]) and np.array_equal(got[0],
                                                              want[0])
    return np.asarray(got)[()] == np.asarray(want)[()]


@pytest.mark.parametrize("name,spec", PROGRAMS)
@pytest.mark.parametrize("n", [1024, 16 * 1024 * 25, 1 << 20])
def test_reference_matches_run_oracle(name, spec, n):
    spec = dict(json.loads(spec), name=name)
    data = _data(spec["dtype"], n, n)
    want = run_oracle(loadgen.program(spec), data)
    got = named.program_kind(spec).answer(spec, data.view(np.uint8))
    if spec["reduce"] == "sum" and spec["dtype"] == "float32":
        # two float64 summation orders over the same float32 elements
        assert abs(float(got) - float(want)) <= 1e-12 * abs(float(want))
    else:
        assert _same(got, want)


@pytest.mark.parametrize("name,spec", PROGRAMS)
def test_reference_on_an_empty_selection(name, spec):
    spec = dict(json.loads(spec), name=name)
    data = np.zeros(1024, spec["dtype"]) - 1
    assert _same(named.program_kind(spec).answer(spec, data.view(np.uint8)),
                 run_oracle(loadgen.program(spec), data))


@pytest.mark.parametrize("name", sorted(FIELD["programs"]))
@pytest.mark.parametrize("rows", [0, 128, 30000])
def test_field_kind_matches_run_oracle(name, rows):
    kind = named.load("programs", "field", NEW_CELL)
    spec = dict(FIELD["programs"][name], name=name)
    rng = np.random.default_rng(rows)
    rec = rng.integers(-2**31, 2**31 - 1, (rows, spec["stride"]),
                       dtype=np.int32)
    rec[:, spec["index"]] = rng.integers(0, 10**9, rows, dtype=np.int32)
    want = run_oracle(kind.build(spec), rec.reshape(-1))
    assert kind.answer(spec, rec.view(np.uint8)) == want
    assert kind.compare(spec, want, want) == ("field_gap", 0)
