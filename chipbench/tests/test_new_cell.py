"""A cell made of new files only: a copy of the harness takes a record
generator (``zones/records.py``), a ``field`` program kind (FIELD, CMP,
SUM: a shape the ``filter`` kind cannot send), a metric reader, a
configuration, a mix and new entries in ``BENCHMARK.json``, and runs it
without a file that was there before changing. The run, on the CPU past the
look for a chip, comes out correct; with the answer altered where it is
produced, not correct.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, NEW_CELL, ROOT

CELL = "records.field-scan"
SEED = 2**33 + 4242
# run in a process of its own, so that the copy's modules are the ones found
DRIVE = """
import json, sys
sys.path[:0] = [sys.argv[1] + "/chipbench", sys.argv[2]]
import run
cell = run.load_cell(sys.argv[3])
mutate = None
if sys.argv[5] == "alter":
    def mutate(dep):
        import repro.core.csd as csd
        orig = csd.execute_extent
        def wrong(*a, **k):
            res = orig(*a, **k)
            res.value = res.value + 1
            return res
        csd.execute_extent = wrong
res = run.run_cell(cell, int(sys.argv[4]), 0.5, False, require_tpu=False,
                   mutate=mutate)
print(json.dumps(res, default=float))
"""


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "chipbench", ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digests(root)
    for folder in ("zones", "programs", "metrics", "configs", "traffic"):
        for src in (NEW_CELL / folder).iterdir():
            dst = root / "chipbench" / folder / src.name
            assert not dst.exists(), dst
            shutil.copy(src, dst)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for key, entries in json.loads(
            (NEW_CELL / "entries.json").read_text()).items():
        bench[key] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root, before


def test_no_file_that_was_there_changed(copy):
    root, before = copy
    after = _digests(root)
    assert set(before) < set(after)
    changed = [p for p in before if before[p] != after[p]]
    assert changed == [root.joinpath("BENCHMARK.json").relative_to(root)]
    old = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = json.loads((root / "BENCHMARK.json").read_text())
    for key, value in old.items():
        if isinstance(value, list):
            assert new[key][:len(value)] == value
        else:
            assert new[key] == value


def _run(root, how):
    p = subprocess.run(
        [sys.executable, "-c", DRIVE, str(root), str(ROOT / "src"), CELL,
         str(SEED), how],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_new_cell_is_correct(copy):
    res = _run(copy[0], "sound")
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res["checks"]) == ["field_gap", "extent_wrong", "unanswered"]
    assert set(res["metrics"]) == {"field_gib_s", "setup_s"}


def test_the_new_cell_with_an_answer_altered_is_not_correct(copy):
    res = _run(copy[0], "alter")
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["field_gap"]["value"] >= 1
