"""Zoned KV-cache manager: the ZNS abstraction applied to serving.

A KV cache is append-only storage: each decode step appends one token's K/V
and nothing is ever updated in place — exactly the write model ZNS zones
mandate. The manager maps sequences onto fixed-size KV zones from a shared
pool (HBM analogue of the device's zone pool):

  * a sequence owns an ordered list of zones (its "zone table" row);
  * appending K/V advances the active zone's write pointer; when full, a new
    zone is allocated (zone transition EMPTY -> OPEN -> FULL);
  * evicting a sequence = host-managed ``reset`` of its zones back to the
    pool (the paper's GC primitive — no device-side GC ever moves data);
  * attention over a sequence's history is computed by the paged Pallas
    kernel directly against the zone pool (repro.kernels.paged_attn).

This gives serving the same fragmentation-free, explicitly-managed memory
model vLLM gets from PagedAttention, derived here from ZNS semantics.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attn.ops import paged_attention
from repro.telemetry.metrics import MetricsRegistry, StatsView

_POOL_SEQ = itertools.count()

__all__ = ["KVZonePool", "KVZoneError"]


class KVZoneError(Exception):
    pass


@dataclass
class _SeqState:
    zones: list[int] = field(default_factory=list)
    length: int = 0


class KVZonePool:
    """num_zones zones of zone_len tokens each, [KV, head_dim] per token."""

    def __init__(self, *, num_zones: int, zone_len: int, kv_heads: int,
                 head_dim: int, max_zones_per_seq: int,
                 dtype=jnp.bfloat16):
        self.num_zones = num_zones
        self.zone_len = zone_len
        self.max_zones_per_seq = max_zones_per_seq
        self.k = jnp.zeros((num_zones, zone_len, kv_heads, head_dim), dtype)
        self.v = jnp.zeros((num_zones, zone_len, kv_heads, head_dim), dtype)
        self._free = list(range(num_zones))
        self._seqs: dict[int, _SeqState] = {}
        # pool counters on a private registry (pools are unbounded);
        # `stats` keeps its dict shape as a live view
        self.metrics = MetricsRegistry(f"kvpool{next(_POOL_SEQ)}")
        self._c_alloc = self.metrics.counter("zones_allocated")
        self._c_reset = self.metrics.counter("zones_reset")
        self._c_tokens = self.metrics.counter("tokens_appended")
        self.stats = StatsView({"zones_allocated": self._c_alloc,
                                "zones_reset": self._c_reset,
                                "tokens_appended": self._c_tokens})

    # ---------------------------------------------------------- lifecycle
    def add_sequence(self, seq_id: int) -> None:
        if seq_id in self._seqs:
            raise KVZoneError(f"sequence {seq_id} exists")
        self._seqs[seq_id] = _SeqState()

    def evict(self, seq_id: int) -> None:
        """Host-managed GC: reset the sequence's zones back to the pool."""
        st = self._seqs.pop(seq_id, None)
        if st is None:
            return
        for z in st.zones:
            self._free.append(z)
        self._c_reset.inc(len(st.zones))

    def _alloc_zone(self, st: _SeqState) -> int:
        if len(st.zones) >= self.max_zones_per_seq:
            raise KVZoneError("sequence exceeds max_zones_per_seq")
        if not self._free:
            raise KVZoneError("zone pool exhausted (evict something)")
        z = self._free.pop(0)
        st.zones.append(z)
        self._c_alloc.inc()
        return z

    # ------------------------------------------------------------- append
    def append(self, seq_id: int, k_tok: jnp.ndarray, v_tok: jnp.ndarray):
        """Append one token's K/V ([KV, head_dim]) — the Zone Append."""
        st = self._seqs[seq_id]
        slot = st.length % self.zone_len
        if slot == 0:
            self._alloc_zone(st)
        z = st.zones[-1]
        self.k = self.k.at[z, slot].set(k_tok.astype(self.k.dtype))
        self.v = self.v.at[z, slot].set(v_tok.astype(self.v.dtype))
        st.length += 1
        self._c_tokens.inc()

    # ---------------------------------------------------------- attention
    def zone_table(self, seq_ids: list[int]) -> tuple[jnp.ndarray, jnp.ndarray]:
        tab = np.full((len(seq_ids), self.max_zones_per_seq), -1, np.int32)
        lengths = np.zeros((len(seq_ids),), np.int32)
        for i, sid in enumerate(seq_ids):
            st = self._seqs[sid]
            tab[i, : len(st.zones)] = st.zones
            lengths[i] = st.length
        return jnp.asarray(tab), jnp.asarray(lengths)

    def attend(self, seq_ids: list[int], q: jnp.ndarray) -> jnp.ndarray:
        """q: [B, H, head_dim] (B == len(seq_ids)). Flash-decode over the
        zone pool via the Pallas kernel."""
        tab, lengths = self.zone_table(seq_ids)
        return paged_attention(q, self.k, self.v, tab, lengths)

    def utilization(self) -> float:
        used = self.num_zones - len(self._free)
        return used / self.num_zones
