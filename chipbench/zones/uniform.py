"""Integers drawn uniformly from ``[low, high)``::

    {"zone": 0, "dtype": "int32", "dist": "uniform", "low": 0, "high": 2147483647}

The zone is full: every element of its capacity is drawn.
"""
from __future__ import annotations

import numpy as np


def fill(spec: dict, g: np.random.Generator, out: np.ndarray,
         start: int) -> None:
    """Draw ``out``, the chunk of the zone's elements from ``start``."""
    out[:] = g.integers(spec["low"], spec["high"], out.size, dtype=out.dtype)
