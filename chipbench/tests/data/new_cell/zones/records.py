"""Fixed-stride int32 records, ``rows`` of them, fewer than the zone holds:
word 0 the row number, word 1 a value drawn uniformly from ``[0, bound)``,
the other words uniform int32::

    {"zone": 0, "dtype": "int32", "dist": "records", "rows": 30000,
     "stride": 8, "bound": 1000000000}

A chunk holds whole records: the stride divides the chunk's elements.
"""
from __future__ import annotations

import numpy as np


def elements(spec: dict, capacity: int) -> int:
    return int(spec["rows"]) * int(spec["stride"])


def fill(spec: dict, g: np.random.Generator, out: np.ndarray,
         start: int) -> None:
    stride = int(spec["stride"])
    if start % stride or out.size % stride:
        raise ValueError(f"stride {stride} splits a chunk's records")
    rec = out.reshape(-1, stride)
    info = np.iinfo(out.dtype)
    rec[:] = g.integers(info.min, info.max, rec.shape, dtype=out.dtype,
                        endpoint=True)
    rec[:, 0] = start // stride + np.arange(len(rec))
    rec[:, 1] = g.integers(0, spec["bound"], len(rec), dtype=out.dtype)
