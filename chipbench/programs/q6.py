"""TPC-H Q6, "Forecasting Revenue Change" (spec §2.4.6), over ``lineitem``
records of 32 int32 words (``zones/lineitem.py``)::

    {"kind": "q6", "date": "1994-01-01", "discount": 6, "quantity": 24}

    SELECT SUM(l_extendedprice * l_discount) FROM lineitem
    WHERE l_shipdate >= DATE AND l_shipdate < DATE + 1 year
      AND l_discount BETWEEN DISCOUNT - 0.01 AND DISCOUNT + 0.01
      AND l_quantity < QUANTITY

in the records' integer units: dates in days since 1970-01-01, the price in
cents, the discount in hundredths (so the BETWEEN is ``D - 1 <= l_discount
<= D + 1`` exactly), the revenue an int64 in 0.0001 currency units.

:func:`answer` is plain numpy from that statement over the raw bytes; only
:func:`build` imports ``repro``. :func:`control` is one precision step
below: the products summed in float32, page by page, as the page scan folds
its carry.

* ``revenue_gap`` widest |answer - reference| over the answers.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import named

NUMBERS = {"revenue_gap": "widest"}

STRIDE = 32
QUANTITY, EXTENDEDPRICE, DISCOUNT, SHIPDATE = 4, 5, 6, 8
RECORDS_PER_PAGE = 4096 // (STRIDE * 4)


def _bounds(spec: dict) -> dict:
    """The predicates' bounds in the records' units."""
    day = np.datetime64(spec["date"], "D")
    year = day.astype("datetime64[Y]")
    if day != year:
        raise ValueError(f"DATE {spec['date']} is not the first of January")
    epoch = np.datetime64("1970-01-01", "D")
    next_year = (year + 1).astype("datetime64[D]")
    d = int(spec["discount"])
    return {"date_lo": int((day - epoch).astype(int)),
            "date_hi": int((next_year - epoch).astype(int)),
            "disc_lo": d - 1, "disc_hi": d + 1,
            "qty_lt": int(spec["quantity"])}


def build(spec: dict):
    """The system's ``Program`` for ``spec``."""
    from repro.core.programs import tpch_q6
    prog = tpch_q6(STRIDE, shipdate=SHIPDATE, discount=DISCOUNT,
                   quantity=QUANTITY, extendedprice=EXTENDEDPRICE,
                   **_bounds(spec))
    return dataclasses.replace(prog, name=spec["name"])


def _products(spec: dict, raw: np.ndarray) -> np.ndarray:
    """Each record's extendedprice × discount as int64, 0 where a predicate
    fails."""
    rec = np.asarray(raw).reshape(-1).view(np.int32).reshape(-1, STRIDE)
    b = _bounds(spec)
    ship, disc = rec[:, SHIPDATE], rec[:, DISCOUNT]
    keep = ((ship >= b["date_lo"]) & (ship < b["date_hi"])
            & (disc >= b["disc_lo"]) & (disc <= b["disc_hi"])
            & (rec[:, QUANTITY] < b["qty_lt"]))
    prod = rec[:, EXTENDEDPRICE].astype(np.int64) * disc.astype(np.int64)
    return np.where(keep, prod, 0)


def answer(spec: dict, raw: np.ndarray) -> np.int64:
    """The exact revenue over ``raw``, the extent's bytes."""
    return np.int64(_products(spec, raw).sum(dtype=np.int64))


def control(spec: dict, raw: np.ndarray) -> np.int64:
    """The revenue with the products summed in float32, page by page."""
    vals = _products(spec, raw).astype(np.float32)
    pagewise = named.load("programs", "filter")._pagewise_f32
    return np.int64(pagewise(vals, RECORDS_PER_PAGE))


def compare(spec: dict, got, want) -> tuple[str, int]:
    return "revenue_gap", abs(int(got) - int(want))
