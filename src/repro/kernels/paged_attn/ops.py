"""Public jit'd wrapper for zoned-KV paged decode attention."""
from __future__ import annotations

import jax

from repro.kernels.paged_attn.kernel import paged_attention_pallas

__all__ = ["paged_attention"]


@jax.jit
def paged_attention(q, k_zones, v_zones, zone_table, lengths):
    """Flash-decode over an append-only zoned KV pool (see kernel.py)."""
    return paged_attention_pallas(q, k_zones, v_zones, zone_table, lengths)
