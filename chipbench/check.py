"""The comparison that decides ``correct``.

Every answer the window produced is held against the plain reference of its
program's kind (``programs/<kind>.py``: its ``answer``, importing nothing of
the system) over the benchmark's own copy of the zone bytes, and every
acknowledged append is read back through the storage's read path. The kind
says which number each answer reads (its ``compare``) and how that number
folds over the window (its ``NUMBERS``): the widest gap, or a count of wrong
answers. Besides, whatever the kind:

* ``extent_wrong`` answered offloads whose reported bytes read differ from
  the extent the benchmark asked for;
* ``append_bad``   acknowledged appended blocks that do not read back;
* ``unanswered``   requests that failed, were refused or never completed.

Each number is held to the limit the mix file gives it. The extent of each
answer is the benchmark's own: the blocks the job names, or the rest of the
zone as the benchmark filled it. The reference reads exactly those bytes,
whatever the program reports.
"""
from __future__ import annotations

import numpy as np

import named

__all__ = ["readings", "judge"]

HARNESS = ("extent_wrong", "append_bad", "unanswered")


def _extent(dep, job, n_blocks: int) -> np.ndarray:
    bb = dep.block_bytes
    raw = dep.data[job.zone].reshape(-1).view(np.uint8)
    return raw[job.block_off * bb:(job.block_off + n_blocks) * bb]


def readings(records, dep, specs: dict, control: bool = False) -> dict:
    """The compared numbers over ``records`` (offloads and appends): of the
    program's answers, or with ``control`` of the control's answers (the
    reference one precision step below, put in the program's place)."""
    out = {"unanswered": 0}
    refs: dict = {}
    lows: dict = {}
    order: dict = {}      # the kinds' numbers met, as an ordered set
    for rec in records:
        if not rec.ok:
            out["unanswered"] += 1
            continue
        job = rec.job
        if job.kind == "append":
            got = np.asarray(dep.storage.read_blocks(
                job.zone, int(rec.value), job.n_blocks)).reshape(-1)
            want = np.frombuffer(job.payload, np.uint8)
            bad = (got[:want.size].reshape(job.n_blocks, -1)
                   != want.reshape(job.n_blocks, -1)).any(axis=1)
            out["append_bad"] = out.get("append_bad", 0) + int(bad.sum())
            continue
        spec = specs[job.program]
        kind = named.program_kind(spec)
        order.update(dict.fromkeys(kind.NUMBERS))
        if not control:
            out["extent_wrong"] = out.get("extent_wrong", 0) + int(
                rec.reported_bytes != rec.n_blocks * dep.block_bytes)
        key = (job.program, job.zone, job.block_off, rec.n_blocks)
        data = _extent(dep, job, rec.n_blocks)
        if key not in refs:
            refs[key] = kind.answer(spec, data)
        want = refs[key]
        if control:
            if key not in lows:
                lows[key] = kind.control(spec, data)
            got = lows[key]
        else:
            got = rec.value
        name, v = kind.compare(spec, got, want)
        if kind.NUMBERS[name] == "widest":
            _widest(out, name, v)
        else:
            out[name] = out.get(name, 0) + int(v)
    # the kinds' numbers in the order they declare them, then the harness's
    return {n: out[n] for n in [*order, *HARNESS] if n in out}


def _widest(out: dict, name: str, v) -> None:
    out[name] = max(out.get(name, v), v)


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit. A number the mix gives
    no limit is a fault of the mix, not a pass."""
    shown, ok = {}, True
    for name in numbers:
        if name not in limits:
            raise KeyError(f"the mix sets no limit for {name}")
        v, lim = numbers[name], limits[name]
        shown[name] = {"value": v, "limit": lim}
        ok = ok and v <= lim
    return ok, shown
