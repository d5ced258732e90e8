"""What the existing cells read does not move when the harness finds its
parts by name: the same zone bytes for the same seed, and the same
``Program`` for every program the scan and YCSB-E mixes send.

The digests were taken with the harness as it was before zone generators
moved into ``zones/<dist>.py``, over 3.5 generator chunks (the last one
partial) of each generator's zone spec.
"""
import hashlib
import json

import numpy as np
import pytest

from conftest import BENCH, NEW_CELL

import deploy
import loadgen
import named

# the zone spec of zcsd-fig2 and of zns-raid0x4's zone 0, and of ``normal``
UNIFORM = {"zone": 0, "dtype": "int32", "dist": "uniform", "low": 0,
           "high": 2147483647}
NORMAL = {"zone": 2, "dtype": "float32", "dist": "normal", "scale": 100.0}
N_BYTES = 7 * deploy._CHUNK * 4 // 2
DIGESTS = [
    (2**33 + 12345, UNIFORM,
     "2462932d767e753e67850d6fd4c5e8dec9227a9c7d7bc1ef91701c9915903308"),
    (2**33 + 12345, NORMAL,
     "d00a4451dd2394f4269c6d35f3a6ee3d69ad340777f75313057c345263fd31be"),
    (-7, UNIFORM,
     "dbaacfdeccc38798400943d4371173e2b0e9166c985eac0568472947d706c00d"),
    (-7, NORMAL,
     "08b84e001544825ba64bfa5fc720d1febeda9947c8b1a243da275ceac38bfe7a"),
]


def test_the_configs_zone_specs_are_the_digested_ones():
    for name in ("zcsd-fig2", "zns-raid0x4"):
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        filled = [z for z in cfg["zones"] if z["dist"] != "empty"]
        assert filled == [UNIFORM]


@pytest.mark.parametrize("seed,spec,digest", DIGESTS,
                         ids=[f"{s}-{z['dist']}" for s, z, _ in DIGESTS])
def test_zone_bytes_match_the_parent_harness(seed, spec, digest):
    data = deploy.zone_data(spec, seed, N_BYTES, 4096)
    assert data.nbytes == N_BYTES
    assert hashlib.sha256(data.tobytes()).hexdigest() == digest


def test_an_empty_zone_generates_nothing():
    spec = {"zone": 1, "dtype": "uint8", "dist": "empty"}
    assert deploy.zone_data(spec, 5, 1 << 20, 4096).size == 0


def test_a_generator_sees_its_chunk_and_may_fill_less(monkeypatch):
    """Row numbers run on across chunks, and a table of fewer rows than the
    zone holds is zero-padded to whole blocks."""
    gen = named.load("zones", "records", NEW_CELL)
    monkeypatch.setattr(named, "zone_kind", lambda spec: gen)
    rows = 3 * deploy._CHUNK // 8 + 5
    spec = {"zone": 0, "dtype": "int32", "dist": "records", "rows": rows,
            "stride": 8, "bound": 1000}
    data = deploy.zone_data(spec, 11, 64 << 20, 4096)
    assert data.nbytes % 4096 == 0 and data.size >= rows * 8
    assert data.nbytes - rows * 32 < 4096
    rec = data[:rows * 8].reshape(rows, 8)
    assert (rec[:, 0] == np.arange(rows)).all()
    assert ((rec[:, 1] >= 0) & (rec[:, 1] < 1000)).all()
    assert not data[rows * 8:].any()
    again = deploy.zone_data(spec, 11, 64 << 20, 4096)
    assert (again == data).all()


def test_a_generator_asking_for_more_than_the_zone_is_refused(monkeypatch):
    gen = named.load("zones", "records", NEW_CELL)
    monkeypatch.setattr(named, "zone_kind", lambda spec: gen)
    spec = {"zone": 0, "dtype": "int32", "dist": "records", "rows": 1 << 20,
            "stride": 8, "bound": 1000}
    with pytest.raises(ValueError, match="records"):
        deploy.zone_data(spec, 11, 1 << 20, 4096)


def _expected():
    from repro.core.programs import Instruction, OpCode, Program
    count = Program("int32", (Instruction(OpCode.CMP_GT, 1073741823),
                              Instruction(OpCode.RED_COUNT)),
                    name="count_gt_half")
    total = Program("int32", (Instruction(OpCode.CMP_GT, 1073741823),
                              Instruction(OpCode.RED_SUM)),
                    name="sum_gt_half")
    return {"fig2-scan": {"count_gt_half": count},
            "array-scan": {"count_gt_half": count},
            "ycsb-e": {"count_gt_half": count, "sum_gt_half": total}}


@pytest.mark.parametrize("mix", ["fig2-scan", "array-scan", "ycsb-e"])
def test_programs_of_the_existing_mixes_are_unchanged(mix):
    want = _expected()[mix]
    specs = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    got = {n: loadgen.program(dict(s, name=n))
           for n, s in specs["programs"].items()}
    assert got == want


def test_a_kind_builds_the_program_it_names():
    from repro.core.programs import field_reduce
    mix = json.loads((NEW_CELL / "traffic" / "field-scan.json").read_text())
    spec = dict(mix["programs"]["sum_price_lt_half"], name="p")
    prog = named.load("programs", "field", NEW_CELL).build(spec)
    ref = field_reduce("int32", 8, 1, "sum", "lt", 500000000)
    assert prog.insns == ref.insns and prog.name == "p"
