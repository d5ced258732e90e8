"""Floats drawn from a normal distribution of mean 0 and deviation
``scale``::

    {"zone": 0, "dtype": "float32", "dist": "normal", "scale": 100.0}

The zone is full: every element of its capacity is drawn.
"""
from __future__ import annotations

import numpy as np


def fill(spec: dict, g: np.random.Generator, out: np.ndarray,
         start: int) -> None:
    """Draw ``out``, the chunk of the zone's elements from ``start``."""
    g.standard_normal(out=out, dtype=out.dtype)
    out *= out.dtype.type(spec["scale"])
