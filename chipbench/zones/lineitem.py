"""TPC-H ``lineitem`` rows as fixed records of 32 int32 words (128 B)::

    {"zone": 0, "dtype": "int32", "dist": "lineitem", "rows": 6001215}

The layout, one word a numeric column in integer units:

====  ===================  ==============================================
word  column               value (TPC-H spec §4.2.3)
====  ===================  ==============================================
0     ``l_orderkey``       from the row number (see ``assumed``)
1     ``l_partkey``        uniform in 1..200,000 (SF 1)
2     ``l_suppkey``        dbgen's formula over ``l_partkey``, 10,000 suppliers
3     ``l_linenumber``     from the row number, 1..4
4     ``l_quantity``       uniform in 1..50, in units
5     ``l_extendedprice``  quantity × ``p_retailprice``, in cents
6     ``l_discount``       uniform in 0..10, in hundredths
7     ``l_tax``            uniform in 0..8, in hundredths
8     ``l_shipdate``       orderdate + 1..121 days, days since 1970-01-01
9     ``l_commitdate``     orderdate + 30..90 days
10    ``l_receiptdate``    shipdate + 1..30 days
11    flags                ``l_returnflag`` | ``l_linestatus`` << 8 (ASCII)
12–31 text                 ``l_shipinstruct`` 25 B, ``l_shipmode`` 10 B,
                           ``l_comment`` 44 B, 1 B of padding
====  ===================  ==============================================

``p_retailprice`` in cents is 90000 + ((partkey / 10) mod 20001) + 100 ×
(partkey mod 1000); orderdate is uniform from 1992-01-01 to 1998-08-02
(ENDDATE − 151 days), one per order. A chunk is generated on its own: its
rows' order keys and line numbers follow from ``start``. The zone holds
``rows`` records, or as many as its capacity holds, zero-padded to whole
blocks.
"""
from __future__ import annotations

import numpy as np

STRIDE = 32
LINES_PER_ORDER = 4
PARTS = 200_000          # SF × 200,000
SUPPLIERS = 10_000       # SF × 10,000
_EPOCH = np.datetime64("1970-01-01", "D")
STARTDATE = int((np.datetime64("1992-01-01", "D") - _EPOCH).astype(int))
LAST_ORDERDATE = int((np.datetime64("1998-08-02", "D") - _EPOCH).astype(int))
CURRENTDATE = int((np.datetime64("1995-06-17", "D") - _EPOCH).astype(int))

_INSTRUCT = [b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
             b"TAKE BACK RETURN"]
_MODES = [b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB"]


def _padded(words: list[bytes], width: int) -> np.ndarray:
    out = np.zeros((len(words), width), np.uint8)
    for i, w in enumerate(words):
        out[i, :len(w)] = np.frombuffer(w, np.uint8)
    return out


_INSTRUCT_B = _padded(_INSTRUCT, 25)
_MODES_B = _padded(_MODES, 10)


def elements(spec: dict, capacity: int) -> int:
    """``rows`` records, or as many whole records as ``capacity`` holds."""
    return min(int(spec["rows"]), capacity // STRIDE) * STRIDE


def retailprice(partkey: np.ndarray) -> np.ndarray:
    """``p_retailprice`` of ``partkey``, in cents (§4.2.3)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def fill(spec: dict, g: np.random.Generator, out: np.ndarray,
         start: int) -> None:
    """Fill ``out``, whole records from element ``start``, from ``g``."""
    if start % STRIDE or out.size % STRIDE:
        raise ValueError("a chunk must hold whole lineitem records")
    rec = out.reshape(-1, STRIDE)
    n = len(rec)
    row = start // STRIDE + np.arange(n, dtype=np.int64)
    order = row // LINES_PER_ORDER
    # dbgen's sparse order keys: the first 8 of every 32
    rec[:, 0] = (order // 8) * 32 + order % 8 + 1
    rec[:, 3] = row % LINES_PER_ORDER + 1
    first = order[0]
    odate = g.integers(STARTDATE, LAST_ORDERDATE, order[-1] - first + 1,
                       endpoint=True)[order - first]
    partkey = g.integers(1, PARTS, n, endpoint=True)
    rec[:, 1] = partkey
    i = g.integers(0, 3, n, endpoint=True)
    rec[:, 2] = (partkey + i * (SUPPLIERS // 4 + (partkey - 1) // SUPPLIERS)) \
        % SUPPLIERS + 1
    qty = g.integers(1, 50, n, endpoint=True)
    rec[:, 4] = qty
    rec[:, 5] = qty * retailprice(partkey)
    rec[:, 6] = g.integers(0, 10, n, endpoint=True)
    rec[:, 7] = g.integers(0, 8, n, endpoint=True)
    ship = odate + g.integers(1, 121, n, endpoint=True)
    receipt = ship + g.integers(1, 30, n, endpoint=True)
    rec[:, 8] = ship
    rec[:, 9] = odate + g.integers(30, 90, n, endpoint=True)
    rec[:, 10] = receipt
    returned = np.where(g.integers(0, 1, n, endpoint=True) == 1,
                        ord("R"), ord("A"))
    rflag = np.where(receipt <= CURRENTDATE, returned, ord("N"))
    lstatus = np.where(ship > CURRENTDATE, ord("O"), ord("F"))
    rec[:, 11] = rflag | lstatus << 8
    text = np.zeros((n, 80), np.uint8)
    text[:, :25] = _INSTRUCT_B[g.integers(0, len(_INSTRUCT), n)]
    text[:, 25:35] = _MODES_B[g.integers(0, len(_MODES), n)]
    length = g.integers(10, 43, (n, 1), endpoint=True)
    letters = g.integers(ord("a"), ord("z"), (n, 44), np.uint8, endpoint=True)
    text[:, 35:79] = np.where(np.arange(44) < length, letters, 0)
    rec[:, 12:] = text.view("<i4")
