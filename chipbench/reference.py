"""Plain numpy reference for the offload programs the mixes send, and its
lower-precision control.

Written from the program semantics (a filter ``x <cmp> threshold`` over the
typed elements of a zone extent, then one terminal), not from the code under
test: nothing here imports ``repro``. A program is the mix file's spec::

    {"name": "sum_gt_half", "dtype": "int32",
     "filter": ["gt", 1073741823], "reduce": "sum"}

``filter`` may be null; ``reduce`` is ``count``, ``sum``, ``min``, ``max`` or
``select`` (with ``capacity``).

Semantics, as the configurations state them:

* COUNT is an int64 count of the elements that pass the filter;
* SUM accumulates in int64 for integer elements and float64 for float ones;
* MIN/MAX return an element of the zone's dtype, or the dtype's identity
  (its max for MIN, its lowest for MAX) when nothing passes;
* SELECT returns the first ``capacity`` passing elements in logical order,
  zero-filled, and the count of all passing elements.

:func:`control` computes the same answers one precision step below what the
configuration states, as a later change might be tempted to: elements
compared and counted in float32 for int32 zones, int32 for int64 integer
sums, float32 for float64 sums and bfloat16 for float32 elements.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

__all__ = ["answer", "control", "PAGE_ELEMS_BYTES"]

# the programs scan 4 KiB pages; the control folds its carry page by page,
# as the page scan does
PAGE_ELEMS_BYTES = 4096

_CMP = {
    "gt": np.greater, "ge": np.greater_equal, "lt": np.less,
    "le": np.less_equal, "eq": np.equal, "ne": np.not_equal,
}


def _mask(spec: dict, x: np.ndarray) -> np.ndarray:
    f = spec.get("filter")
    if not f:
        return np.ones(x.shape, bool)
    cmp, thr = f
    return _CMP[cmp](x, x.dtype.type(thr))


def _identity(reduce: str, dtype: np.dtype):
    info = np.iinfo(dtype) if dtype.kind in "iu" else np.finfo(dtype)
    return dtype.type(info.max if reduce == "min" else info.min)


def answer(spec: dict, data: np.ndarray):
    """The exact answer of ``spec`` over ``data`` (the extent's elements in
    logical order)."""
    dtype = np.dtype(spec["dtype"])
    x = np.asarray(data).reshape(-1).view(dtype)
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


def control(spec: dict, data: np.ndarray):
    """The answer computed one precision step below the stated one (see the
    module docstring); comparable with :func:`answer`'s result."""
    dtype = np.dtype(spec["dtype"])
    x = np.asarray(data).reshape(-1).view(dtype)
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
