"""Project one word of fixed-stride records, filter it, sum it: the
system's ``field_reduce`` (FIELD, CMP, SUM)::

    {"kind": "field", "dtype": "int32", "stride": 8, "index": 1,
     "filter": ["lt", 500000000]}

The sum is exact in int64 over the records whose word passes the filter.
:func:`answer` is plain numpy from that statement; only :func:`build`
imports ``repro``. :func:`control` is one precision step below: the word
compared in float32 and summed in int32.

* ``field_gap`` widest |answer - reference| over the answers.
"""
from __future__ import annotations

import dataclasses

import numpy as np

NUMBERS = {"field_gap": "widest"}

_CMP = {"gt": np.greater, "ge": np.greater_equal, "lt": np.less,
        "le": np.less_equal, "eq": np.equal, "ne": np.not_equal}


def build(spec: dict):
    from repro.core.programs import field_reduce
    cmp, thr = spec["filter"]
    prog = field_reduce(spec["dtype"], int(spec["stride"]),
                        int(spec["index"]), "sum", cmp, thr)
    return dataclasses.replace(prog, name=spec["name"])


def _word(spec: dict, raw: np.ndarray) -> np.ndarray:
    x = np.asarray(raw).reshape(-1).view(np.dtype(spec["dtype"]))
    return x.reshape(-1, int(spec["stride"]))[:, int(spec["index"])]


def answer(spec: dict, raw: np.ndarray) -> np.int64:
    w = _word(spec, raw)
    cmp, thr = spec["filter"]
    return np.int64(w[_CMP[cmp](w, w.dtype.type(thr))].sum(dtype=np.int64))


def control(spec: dict, raw: np.ndarray) -> np.int64:
    w = _word(spec, raw)
    cmp, thr = spec["filter"]
    m = _CMP[cmp](w.astype(np.float32), np.float32(thr))
    return np.int64(w[m].sum(dtype=np.int32))


def compare(spec: dict, got, want) -> tuple[str, int]:
    return "field_gap", abs(int(got) - int(want))
