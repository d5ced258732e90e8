"""Pallas TPU kernel: flash-decode attention over a *zoned* KV cache.

The serving tier stores KV in append-only ZNS-style zones (a KV cache *is*
append-only storage; zone reset = sequence eviction). This kernel computes
one decode step directly against the zone pool — the "compute inside the
storage device" tier for serving:

  * grid = (B, MZ): for each sequence, stream that sequence's zones through
    VMEM one zone at a time. The BlockSpec index_map reads the *scalar-
    prefetched* zone table to pick zone ``zone_table[b, z]`` out of the HBM
    pool — the kernel reads zones in place and never materializes a
    contiguous per-sequence cache;
  * online softmax across zones: running (max, sum, acc) scratch in VMEM
    persists across the inner grid dimension;
  * out-of-range / unused zones are masked via the per-sequence length.

The zone-pool -> VMEM streaming obeys the same "small device memory" tiling
discipline as zone_filter: one zone block (ZL x KV x hd) in VMEM at a time.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import pallas_interpret

__all__ = ["paged_attention_pallas"]


def _decode_kernel(ztab_ref, len_ref, q_ref, k_ref, v_ref, out_ref,
                   m_ref, l_ref, acc_ref, *, zl: int):
    b = pl.program_id(0)
    z = pl.program_id(1)
    mz = pl.num_programs(1)

    @pl.when(z == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                     # [KV, G, hd]
    k = k_ref[0]                                     # [ZL, KV, hd]
    v = v_ref[0]
    hd = q.shape[-1]

    zone_id = ztab_ref[b, z]
    length = len_ref[b]
    pos = z * zl + jax.lax.iota(jnp.int32, zl)
    valid = (pos < length) & (zone_id >= 0)          # [ZL]

    qf = q.astype(jnp.float32) * hd ** -0.5
    logits = jnp.einsum("kgh,skh->kgs", qf, k.astype(jnp.float32))
    logits = jnp.where(valid[None, None, :], logits, -1e30)

    m_prev = m_ref[...]                              # [KV, G]
    m_new = jnp.maximum(m_prev, logits.max(-1))
    p = jnp.exp(logits - m_new[..., None])           # [KV, G, ZL]
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] + jnp.einsum(
        "kgs,skh->kgh", p, v.astype(jnp.float32))
    m_ref[...] = m_new

    @pl.when(z == mz - 1)
    def _final():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        out_ref[0] = out.astype(out_ref.dtype)


def paged_attention_pallas(q, k_zones, v_zones, zone_table, lengths, *,
                           interpret: Optional[bool] = None):
    """q: [B, H, hd]; k_zones/v_zones: [NZ, ZL, KV, hd];
    zone_table: [B, MZ] int32 (-1 = unused); lengths: [B] int32.
    Returns [B, H, hd]. ``interpret`` defaults to
    :func:`repro.runtime.pallas_interpret`."""
    if interpret is None:
        interpret = pallas_interpret()
    B, H, hd = q.shape
    NZ, ZL, KV, _ = k_zones.shape
    MZ = zone_table.shape[1]
    G = H // KV

    qr = q.reshape(B, KV, G, hd)

    def _zone_block(b, z, ztab_ref, len_ref):
        # stream zone `zone_table[b, z]` (clamped for the -1 sentinel; its
        # contribution is masked in the kernel) out of the HBM zone pool
        return (jnp.maximum(ztab_ref[b, z], 0), 0, 0, 0)

    kernel = functools.partial(_decode_kernel, zl=ZL)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # zone_table, lengths
        grid=(B, MZ),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd),
                         lambda b, z, ztab_ref, len_ref: (b, 0, 0, 0)),
            pl.BlockSpec((1, ZL, KV, hd), _zone_block),
            pl.BlockSpec((1, ZL, KV, hd), _zone_block),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd),
                               lambda b, z, ztab_ref, len_ref: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(zone_table, lengths, qr, k_zones, v_zones)
    return out.reshape(B, H, hd)
