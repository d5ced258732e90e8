"""BENCHMARK.json keeps to its contract, and every configuration, mix and
metric it names is found by that name, as is every zone generator and
program kind that a configuration or a mix names."""
import ast
import json
import re
import subprocess
import sys

import pytest

from conftest import BENCH, NEW_CELL, ROOT

import named
import run

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
CONFIGS = sorted((BENCH / "configs").glob("*.json"))
MIXES = sorted((BENCH / "traffic").glob("*.json"))
KINDS = sorted((BENCH / "programs").glob("*.py")) + [
    NEW_CELL / "programs" / "field.py"]


def _one_line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_lines():
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _one_line(c["source"]) and _one_line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and _one_line(w["why"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        names.append(w["name"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert UNIT.match(m["unit"])
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))


def test_per_layer_metrics_name_layer_moves_and_cells():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _one_line(m["layer"])
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in CELLS
            assert cell in e2e[m["moves"]].get("workloads", [cell])


def test_end_to_end_bounds_and_sources():
    names = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in names
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for cell in CELLS:
        reported = [m["name"] for m in SPEC["end_to_end"]
                    if cell in m.get("workloads", [cell])]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m["workloads"] for m in SPEC["per_layer"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_its_config_mix_and_metrics(cell):
    c = run.load_cell(cell)
    assert c.config["name"] == c.workload["config"]
    assert c.config["read_us_per_block"] == 0
    assert c.config["append_us_per_block"] == 0
    assert c.mix["tenants"] and c.mix["limits"]
    for m in c.end_to_end + c.per_layer:
        assert callable(run.load_metric(m["name"]))


def test_config_files_are_their_own():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]


def test_no_tpu_exits_nonzero_without_a_result():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "fig2.scan",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_without_the_system_it_exits_nonzero(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    subprocess.run(["cp", "-r", str(BENCH), str(tmp_path / "chipbench")],
                   check=True)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "fig2.scan",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("path", CONFIGS, ids=[p.stem for p in CONFIGS])
def test_every_dist_resolves_to_a_file(path):
    for spec in json.loads(path.read_text())["zones"]:
        gen = named.zone_kind(spec)
        assert callable(getattr(gen, "fill", None)) \
            or gen.elements(spec, 1 << 20) == 0


@pytest.mark.parametrize("path", MIXES, ids=[p.stem for p in MIXES])
def test_every_kind_resolves_to_a_file(path):
    for spec in json.loads(path.read_text())["programs"].values():
        kind = named.program_kind(spec)
        for part in ("build", "answer", "control", "compare"):
            assert callable(getattr(kind, part))
        assert set(kind.NUMBERS.values()) <= {"widest", "wrong"}


@pytest.mark.parametrize("path", KINDS, ids=[p.stem for p in KINDS])
def test_a_kinds_reference_imports_nothing_of_the_system(path):
    """Only ``build`` may import ``repro``: the reference, the control and
    the comparison are written from the semantics alone."""
    tree = ast.parse(path.read_text())
    found = []
    for node in tree.body:
        if isinstance(node, ast.FunctionDef) and node.name == "build":
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Import):
                found += [a.name for a in sub.names]
            elif isinstance(sub, ast.ImportFrom):
                found.append(sub.module or "")
    assert not [m for m in found if m.split(".")[0] == "repro"], found


def test_an_unknown_dist_fails_at_load_cell_naming_it(tmp_path):
    cfg = json.loads((BENCH / "configs" / "zcsd-fig2.json").read_text())
    cfg["zones"][0]["dist"] = "no-such-dist"
    (tmp_path / "bad.json").write_text(json.dumps(cfg))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        if c["name"] == "zcsd-fig2":
            c["file"] = "bad.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(LookupError, match="no-such-dist"):
        run.load_cell("fig2.scan", root=tmp_path)


def test_an_unknown_kind_fails_at_load_cell_naming_it(tmp_path, monkeypatch):
    mix = json.loads((BENCH / "traffic" / "fig2-scan.json").read_text())
    mix["programs"]["count_gt_half"]["kind"] = "no-such-kind"
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "fig2-scan.json").write_text(json.dumps(mix))
    monkeypatch.setattr(run, "HERE", tmp_path)
    with pytest.raises(LookupError, match="no-such-kind"):
        run.load_cell("fig2.scan")
