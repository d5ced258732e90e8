"""Pallas TPU kernel: streaming filtered reduction over a zone.

This is the paper's Figure 2 hot loop (predicate over 64Mi integers at page
granularity) re-tiled for the TPU memory hierarchy:

  * a zone extent lives in HBM as ``[n_pages, page_elems]``, and the array
    scheduler stacks same-shape stripe chunks into
    ``[n_chunks, n_pages, page_elems]``;
  * the grid is ``(chunk, block)``: it streams fixed *blocks* of pages of one
    chunk through VMEM (``BlockSpec((1, pages_per_block, page_elems))``) --
    the paper's "CSD DRAM is small, process per page" constraint becomes
    "the working set must fit VMEM";
  * each grid step reduces its block over the page (sublane) axis and folds
    the lane-wise partial into that chunk's ``[1, page_elems]`` accumulator,
    which stays resident in VMEM across the ``"arbitrary"`` block axis; only
    the accumulators (one page's worth per chunk, not the zone) leave the
    kernel -- near-data processing at the HBM boundary.

Program transforms (the eBPF-analogue ALU/CMP chain) are traced into the
kernel body as fused elementwise ops, so one kernel serves every verified
program with a reduce terminal.

Alignment: ``page_elems`` (1024 int32 for the paper's 4 KiB pages) is the
whole lane extent of a block; ``pages_per_block`` is all of ``n_pages`` or a
multiple of 8 sublanes, and a last block that runs past ``n_pages`` is
masked, so every extent geometry tiles.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import pallas_interpret

__all__ = ["filtered_reduce_pallas", "filtered_reduce_pallas_batched",
           "DEFAULT_BLOCK_PAGES"]

DEFAULT_BLOCK_PAGES = 512   # 512 pages x 4 KiB = 2 MiB block in VMEM
_SUBLANES = 8


def _pick_block_pages(block_pages: int, n_pages: int) -> int:
    """Pages per VMEM block: all of ``n_pages`` when they fit one block,
    else the largest multiple of 8 sublanes <= ``block_pages`` (at least 8).
    Both are block shapes the TPU tiling accepts for any ``n_pages``."""
    if n_pages <= max(block_pages, _SUBLANES):
        return n_pages
    return max(block_pages // _SUBLANES * _SUBLANES, _SUBLANES)


def _acc_dtype(kind: str, dtype) -> jnp.dtype:
    if kind == "count":
        return jnp.int32
    if kind == "sum":
        return jnp.float32 if dtype.kind == "f" else jnp.int32
    return dtype


def _reduce_kernel(x_ref, out_ref, *, transform, kind, n_pages):
    """One grid step: fold one VMEM block of one chunk into the chunk's
    lane-wise accumulator ``out_ref[0]`` (``[1, page_elems]``)."""
    i = pl.program_id(1)
    vals, mask = transform(x_ref[0])
    bp = mask.shape[0]
    if n_pages % bp:   # ragged last block: rows past n_pages are padding
        row = i * bp + jax.lax.broadcasted_iota(jnp.int32, mask.shape, 0)
        mask = mask & (row < n_pages)
    acc = out_ref.dtype
    # dtypes pinned explicitly: under 64-bit trace mode a Python scalar is a
    # weak int64, which Mosaic cannot narrow, and jnp.sum would promote int32
    # partials to int64 and miss the out_ref dtype
    if kind == "count":
        part = jnp.sum(mask.astype(jnp.int32), axis=0, keepdims=True,
                       dtype=jnp.int32)
        fold = jnp.add
    elif kind == "sum":
        masked = jnp.where(mask, vals, jnp.zeros((), vals.dtype))
        part = jnp.sum(masked.astype(acc), axis=0, keepdims=True, dtype=acc)
        fold = jnp.add
    elif kind in ("min", "max"):
        info = (jnp.finfo if vals.dtype.kind == "f" else jnp.iinfo)(vals.dtype)
        ident = jnp.asarray(info.max if kind == "min" else info.min, vals.dtype)
        red, fold = ((jnp.min, jnp.minimum) if kind == "min"
                     else (jnp.max, jnp.maximum))
        part = red(jnp.where(mask, vals, ident), axis=0, keepdims=True)
    else:
        raise ValueError(kind)

    @pl.when(i == 0)
    def _first():
        out_ref[0] = part

    @pl.when(i > 0)
    def _rest():
        out_ref[0] = fold(out_ref[0], part)


def _combine_partials(partials: jnp.ndarray, kind: str) -> jnp.ndarray:
    """Lane reduce of the ``[n_chunks, 1, page_elems]`` accumulators (fused
    into the same jit as the kernel call)."""
    if kind in ("count", "sum"):
        return partials.sum(axis=(1, 2), dtype=partials.dtype)
    if kind == "min":
        return partials.min(axis=(1, 2))
    return partials.max(axis=(1, 2))


def filtered_reduce_pallas_batched(
    pages: jnp.ndarray,
    *,
    kind: str = "count",
    transform: Optional[Callable] = None,
    block_pages: int = DEFAULT_BLOCK_PAGES,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Chunk-batched filtered reduction: ``[n_chunks, n_pages, page_elems]``
    -> one reduced value per chunk (``[n_chunks]``).

    ``transform(x) -> (vals, mask)`` is the fused program chain (defaults to
    the identity with an all-true mask). Each value is an int32 count, an
    f32/i32 sum, or the dtype min/max. The array scheduler's striped fan-out
    compiles ONE kernel and executes every same-shape stripe chunk in a
    single ``pallas_call``; each chunk's accumulation order depends only on
    its own pages, so results are bit-identical to running chunks one by one.

    ``interpret`` defaults to :func:`repro.runtime.pallas_interpret` (compiled
    on a TPU backend, interpreted elsewhere).
    """
    n_chunks, n_pages, page_elems = pages.shape
    bp = _pick_block_pages(block_pages, n_pages)
    if transform is None:
        transform = lambda x: (x, jnp.ones(x.shape, bool))
    if interpret is None:
        interpret = pallas_interpret()
    acc_dtype = _acc_dtype(kind, pages.dtype)

    kernel = functools.partial(_reduce_kernel, transform=transform, kind=kind,
                               n_pages=n_pages)
    # block indices as int32: under 64-bit trace mode a literal 0 would be
    # an int64 that Mosaic refuses to return from the index map
    zero = lambda: jnp.int32(0)
    partials = pl.pallas_call(
        kernel,
        grid=(n_chunks, pl.cdiv(n_pages, bp)),
        in_specs=[pl.BlockSpec((1, bp, page_elems),
                               lambda c, i: (c, i, zero()))],
        out_specs=pl.BlockSpec((1, 1, page_elems),
                               lambda c, i: (c, zero(), zero())),
        out_shape=jax.ShapeDtypeStruct((n_chunks, 1, page_elems), acc_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pages)
    return _combine_partials(partials, kind)


def filtered_reduce_pallas(pages: jnp.ndarray, **kw) -> jnp.ndarray:
    """Filtered reduction over one zone extent ``[n_pages, page_elems]``: the
    batched kernel with one chunk. Keywords as
    :func:`filtered_reduce_pallas_batched`."""
    return filtered_reduce_pallas_batched(pages[None], **kw)[0]
