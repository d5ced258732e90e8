"""Filter and reduce over the typed elements of a zone extent: a filter
``x <cmp> threshold``, then one terminal. The kind of every program spec that
names none. A spec, as a mix file gives it::

    {"dtype": "int32", "filter": ["gt", 1073741823], "reduce": "sum"}

``filter`` may be null; ``reduce`` is ``count``, ``sum``, ``min``, ``max`` or
``select`` (with ``capacity``).

Semantics, as the configurations state them:

* COUNT is an int64 count of the elements that pass the filter;
* SUM accumulates in int64 for integer elements and float64 for float ones;
* MIN/MAX return an element of the zone's dtype, or the dtype's identity
  (its max for MIN, its lowest for MAX) when nothing passes;
* SELECT returns the first ``capacity`` passing elements in logical order,
  zero-filled, and the count of all passing elements.

:func:`answer` is a plain numpy reference written from these semantics, not
from the code under test: only :func:`build` imports ``repro``.
:func:`control` computes the same answers one precision step below what the
configuration states, as a later change might be tempted to: elements
compared and counted in float32 for int32 zones, int32 for int64 integer
sums, float32 for float64 sums and bfloat16 for float32 elements.

The numbers compared, and how each folds over the answers of a window:

* ``count_gap``    widest |answer - reference| over COUNT answers;
* ``sum_gap``      widest |answer - reference| over integer SUM answers;
* ``fsum_rel_gap`` widest |answer - reference| / |reference| over float SUM
  answers;
* ``minmax_wrong`` MIN/MAX answers that differ from the reference;
* ``select_wrong`` SELECT answers whose elements or count differ.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

NUMBERS = {"count_gap": "widest", "sum_gap": "widest",
           "fsum_rel_gap": "widest", "minmax_wrong": "wrong",
           "select_wrong": "wrong"}

# the programs scan 4 KiB pages; the control folds its carry page by page,
# as the page scan does
PAGE_ELEMS_BYTES = 4096

_CMP = {
    "gt": np.greater, "ge": np.greater_equal, "lt": np.less,
    "le": np.less_equal, "eq": np.equal, "ne": np.not_equal,
}


def build(spec: dict):
    """The system's ``Program`` for ``spec``."""
    from repro.core.programs import Instruction, OpCode, Program
    insns = []
    if spec.get("filter"):
        cmp, thr = spec["filter"]
        insns.append(Instruction(OpCode["CMP_" + cmp.upper()], thr))
    red = spec["reduce"]
    if red == "select":
        insns.append(Instruction(OpCode.SELECT))
        return Program(spec["dtype"], tuple(insns),
                       select_capacity=int(spec["capacity"]),
                       name=spec["name"])
    insns.append(Instruction(OpCode["RED_" + red.upper()]))
    return Program(spec["dtype"], tuple(insns), name=spec["name"])


def _mask(spec: dict, x: np.ndarray) -> np.ndarray:
    f = spec.get("filter")
    if not f:
        return np.ones(x.shape, bool)
    cmp, thr = f
    return _CMP[cmp](x, x.dtype.type(thr))


def _identity(reduce: str, dtype: np.dtype):
    info = np.iinfo(dtype) if dtype.kind in "iu" else np.finfo(dtype)
    return dtype.type(info.max if reduce == "min" else info.min)


def answer(spec: dict, raw: np.ndarray):
    """The exact answer of ``spec`` over ``raw``, the extent's bytes (or its
    elements) in logical order."""
    dtype = np.dtype(spec["dtype"])
    x = np.asarray(raw).reshape(-1).view(dtype)
    m = _mask(spec, x)
    red = spec["reduce"]
    if red == "count":
        return np.int64(np.count_nonzero(m))
    if red == "sum":
        wide = np.int64 if dtype.kind in "iu" else np.float64
        return wide(x[m].sum(dtype=wide))
    if red in ("min", "max"):
        sel = x[m]
        if sel.size == 0:
            return _identity(red, dtype)
        return sel.min() if red == "min" else sel.max()
    if red == "select":
        return _select(spec, x, m)
    raise ValueError(f"unknown reduce {red!r}")


def _select(spec: dict, x: np.ndarray, m: np.ndarray):
    cap = int(spec["capacity"])
    idx = np.flatnonzero(m)
    out = np.zeros(cap, x.dtype)
    take = idx[:cap]
    out[: take.size] = x[take]
    return out, np.int64(idx.size)


def _pagewise_f32(vals: np.ndarray, page: int) -> np.float32:
    """Sum ``vals`` in float32: exact-ish within a page, then a float32 carry
    added page after page (``cumsum`` adds sequentially)."""
    n = vals.size - vals.size % page
    pages = vals[:n].reshape(-1, page).sum(axis=1, dtype=np.float32)
    tail = vals[n:].sum(dtype=np.float32)
    carry = np.cumsum(pages, dtype=np.float32)
    total = carry[-1] if carry.size else np.float32(0)
    return np.float32(total + tail)


def control(spec: dict, raw: np.ndarray):
    """The answer computed one precision step below the stated one (see the
    module docstring); comparable with :func:`answer`'s result."""
    dtype = np.dtype(spec["dtype"])
    x = np.asarray(raw).reshape(-1).view(dtype)
    page = PAGE_ELEMS_BYTES // dtype.itemsize
    red = spec["reduce"]
    low = np.float32 if dtype == np.int32 else ml_dtypes.bfloat16
    if dtype.kind == "f" and dtype.itemsize == 8:
        low = np.float32
    xl = x.astype(low)
    f = spec.get("filter")
    m = np.ones(x.shape, bool) if not f else \
        _CMP[f[0]](xl, np.asarray(f[1]).astype(low))
    if red == "count":
        return np.int64(_pagewise_f32(m.astype(np.float32), page))
    if red == "sum":
        if dtype.kind in "iu":
            return np.int64(x[m].sum(dtype=np.int32))
        return np.float64(_pagewise_f32(
            np.where(m, x, 0).astype(np.float32), page))
    if red in ("min", "max"):
        sel = xl[m]
        if sel.size == 0:
            return _identity(red, dtype)
        v = sel.min() if red == "min" else sel.max()
        return _back(v, dtype)
    if red == "select":
        vals, n = _select(spec, xl, m)
        return _back(vals, dtype), n
    raise ValueError(f"unknown reduce {red!r}")


def _back(v, dtype: np.dtype):
    """Lower-precision values returned in the stated dtype, so they compare
    with the program's answers."""
    v = np.asarray(v)
    if dtype.kind in "iu":
        info = np.iinfo(dtype)
        return np.clip(v.astype(np.float64), info.min, info.max).astype(dtype)
    return v.astype(dtype)


def compare(spec: dict, got, want) -> tuple[str, object]:
    """The number that ``got``, an answer to ``spec``, reads against the
    reference's ``want``: a gap, or whether it is wrong."""
    red = spec["reduce"]
    if red == "count":
        return "count_gap", abs(int(got) - int(want))
    if red == "sum" and np.dtype(spec["dtype"]).kind in "iu":
        return "sum_gap", abs(int(got) - int(want))
    if red == "sum":
        return "fsum_rel_gap", (abs(float(got) - float(want))
                                / max(abs(float(want)), 1e-300))
    if red in ("min", "max"):
        return "minmax_wrong", not np.asarray(got, spec["dtype"])[()] == want
    if red == "select":
        vals, n = got
        same = int(n) == int(want[1]) and np.array_equal(np.asarray(vals),
                                                         want[0])
        return "select_wrong", not same
    raise ValueError(f"unknown reduce {red!r}")
