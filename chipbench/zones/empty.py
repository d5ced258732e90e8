"""A zone that holds nothing at set-up; the mix's appends fill it::

    {"zone": 1, "dtype": "uint8", "dist": "empty"}
"""
from __future__ import annotations


def elements(spec: dict, capacity: int) -> int:
    """No element is generated, whatever the zone's capacity."""
    return 0
