"""The control of each cell comes out not correct under the mix's limits,
while the program's own answers come out correct.

The control is the reference one precision step below what the
configuration states, put in the program's place. A COUNT only goes wrong
in float32 once the count passes 2**24, so the scan cells are held at a
size where it does (256 MiB of int32); the YCSB-E cell, whose int64 sums
go wrong in int32 at once, at a small one.
"""
import pytest

import check
import control
import run

SEEDS = [2**31 + 17, 2**33 + 5, 7]
# member zone bytes: the array cells stripe over four members
SIZES = {"fig2.scan": 256 << 20, "raid0x4.scan": 64 << 20,
         "raid0x4.ycsb-e": 1 << 20}


@pytest.mark.parametrize("cell_name", sorted(SIZES))
@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_and_program_passes(cell_name, seed):
    cell = run.load_cell(cell_name)
    cell.config["zone_bytes"] = SIZES[cell_name]
    r = control.readings(cell, seed, 0.3, True)
    ok, shown = check.judge(r["program"], cell.mix["limits"])
    assert ok, shown
    bad, shown = check.judge(r["control"], cell.mix["limits"])
    assert not bad, shown
