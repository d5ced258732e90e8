"""A whole run of each cell, at a small size on the CPU and past the look for
a chip, comes out correct; with the timed path broken underneath it comes
out not correct, once for each fault the cell can have:

* an answer altered where it is produced (every cell);
* half of the batch left out and the rest counted twice: half the zone's
  pages (one device), or every other stripe chunk's partial (the array);
* half of the batch left out and reported as the whole: asked for a whole
  zone, the entry scans its first half and reports that half's bytes
  (cells whose offloads leave the extent to the entry);
* a step that leaves the state unchanged: appends acknowledged while the
  zone keeps its old bytes (cells that append).

One chip holds every cell, so no exchange between chips can be left out.
"""
import numpy as np
import pytest

import run

SMALL_ZONE = 1 << 20
SEED = 2**33 + 12345


def _cell(name):
    cell = run.load_cell(name)
    cell.config["zone_bytes"] = SMALL_ZONE
    return cell


def _run(name, mutate=None):
    return run.run_cell(_cell(name), SEED, 0.6, False, require_tpu=False,
                        mutate=mutate)


def _alter(v):
    if isinstance(v, tuple):
        vals = np.array(v[0], copy=True)
        vals[0] += 1
        return vals, v[1]
    v = np.asarray(v)
    return (v + 1).astype(v.dtype)


def alter_answer(mp):
    def mutate(dep):
        if dep.config["entry"] == "csd":
            import repro.core.csd as csd
            orig = csd.execute_extent

            def wrong(*a, **k):
                res = orig(*a, **k)
                res.value = _alter(res.value)
                return res
            mp.setattr(csd, "execute_extent", wrong)
        else:
            orig = dep.entry._execute

            def wrong(cmd):
                value, stats = orig(cmd)
                return _alter(value), stats
            mp.setattr(dep.entry, "_execute", wrong)
    return mutate


def drop_half(mp):
    """Half of the pages (one device) or of the chunk partials (the array)
    left out, the other half counted twice in their place."""
    def mutate(dep):
        if dep.config["entry"] == "csd":
            orig = dep.storage.read_extent

            def half(*a, **k):
                x = np.array(orig(*a, **k))
                h = x.size // 2
                x[h:2 * h] = x[:h]
                return x
            mp.setattr(dep.storage, "read_extent", half)
        else:
            from repro.array import scheduler
            orig = scheduler._StagedCombiner._fold_one
            seen = {"n": 0}

            def fold(self, v):
                seen["n"] += 1
                if seen["n"] % 2:
                    orig(self, v)
                    orig(self, v)
            mp.setattr(scheduler._StagedCombiner, "_fold_one", fold)
    return mutate


def truncate_extent(mp):
    """Asked for the whole zone, the entry scans its first half and reports
    that half's bytes: the answer and the byte count agree with each other,
    not with the request."""
    def mutate(dep):
        ent = dep.entry
        name = "nvm_cmd_bpf_run" if dep.config["entry"] == "csd" else "submit"
        orig = getattr(ent, name)

        def half(program, zone_id, *a, block_off=0, n_blocks=None, **k):
            if n_blocks is None:
                n_blocks = (dep.storage.zone(zone_id).write_pointer
                            - block_off) // 2
            return orig(program, zone_id, *a, block_off=block_off,
                        n_blocks=n_blocks, **k)
        mp.setattr(ent, name, half)
    return mutate


def unchanged_state(mp):
    def mutate(dep):
        from repro.zns.device import payload_as_uint8
        for member in dep.storage.devices:
            orig = member._do_append

            def keep(zone_id, data, _orig=orig):
                return _orig(zone_id, np.zeros_like(payload_as_uint8(data)))
            mp.setattr(member, "_do_append", keep)
    return mutate


CASES = [
    ("fig2.scan", alter_answer), ("fig2.scan", drop_half),
    ("fig2.scan", truncate_extent),
    ("raid0x4.scan", alter_answer), ("raid0x4.scan", drop_half),
    ("raid0x4.scan", truncate_extent),
    ("raid0x4.ycsb-e", alter_answer), ("raid0x4.ycsb-e", drop_half),
    ("raid0x4.ycsb-e", unchanged_state),
]


@pytest.mark.parametrize("cell", sorted({c for c, _ in CASES}))
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{c}-{f.__name__}" for c, f in CASES])
def test_broken_path_is_not_correct(cell, fault, monkeypatch):
    res = _run(cell, mutate=fault(monkeypatch))
    assert res["correct"] is False, res["checks"]
