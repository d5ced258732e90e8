"""Execution tiers for verified offload programs.

The paper evaluates three ways to execute the same offloaded computation
(Figure 2); we reproduce all three, plus the TPU-native tier the paper lists
as future hardware backends:

  tier "native"     hand-written host code (paper: SPDK userspace loop)
                    -> :func:`run_oracle` (vectorized numpy; also the test
                    oracle for every other tier)
  tier "interp"     stack-machine VM, one instruction at a time, per-access
                    memory bounds checks (paper: uBPF without JIT)
                    -> :func:`interpret_program`
  tier "jit"        program compiled before execution (paper: uBPF JIT/x86;
                    here: XLA via jax.jit), streamed in tiles of whole pages
                    -> :func:`jit_program`
  tier "kernel"     Pallas TPU kernel streaming zone blocks HBM->VMEM
                    (repro.kernels.zone_filter / zone_reduce; wired up by
                    repro.core.csd.NvmCsd)

All tiers process the zone in whole pages, in page order — the paper's
conservative design for small CSD DRAM. The interpreter takes one page at a
time; the JIT tier takes a tile of :func:`pages_per_step` pages a step, so
that a step's loop overhead is paid on megabytes, not on one 4 KiB page.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.programs import (
    CMP_OPS,
    RECORD_OPS,
    Instruction,
    OpCode,
    Program,
)
from repro.runtime import offload_x64

__all__ = [
    "OffloadResult",
    "run_oracle",
    "interpret_program",
    "jit_program",
    "jit_program_batched",
    "JittedProgram",
    "pages_per_step",
]


# ---------------------------------------------------------------------------
# shared semantics helpers
# ---------------------------------------------------------------------------

_SUM_WIDEN = {
    np.dtype(np.int32): np.int64, np.dtype(np.int64): np.int64,
    np.dtype(np.uint32): np.int64,
    np.dtype(np.float32): np.float64, np.dtype(np.float64): np.float64,
}


def _minmax_identity(op: OpCode, dtype: np.dtype):
    info = np.iinfo(dtype) if np.issubdtype(dtype, np.integer) else np.finfo(dtype)
    return info.max if op == OpCode.RED_MIN else info.min


def _apply_alu_np(x: np.ndarray, insn: Instruction) -> np.ndarray:
    op, imm = insn.op, insn.imm
    dt = x.dtype
    with np.errstate(over="ignore"):
        if op == OpCode.ADD:
            return (x + dt.type(imm)).astype(dt)
        if op == OpCode.SUB:
            return (x - dt.type(imm)).astype(dt)
        if op == OpCode.MUL:
            return (x * dt.type(imm)).astype(dt)
        if op == OpCode.AND:
            return x & dt.type(imm)
        if op == OpCode.OR:
            return x | dt.type(imm)
        if op == OpCode.XOR:
            return x ^ dt.type(imm)
        if op == OpCode.SHL:
            return (x << imm).astype(dt)
        if op == OpCode.SHR:
            return (x >> imm).astype(dt)
        if op == OpCode.MOD:
            return (x % dt.type(imm)).astype(dt)
        if op == OpCode.ABS:
            return np.abs(x)
        if op == OpCode.NEG:
            return (-x).astype(dt)
    raise AssertionError(op)


def _apply_cmp_np(x: np.ndarray, insn: Instruction) -> np.ndarray:
    imm = x.dtype.type(insn.imm)
    return {
        OpCode.CMP_GT: x > imm, OpCode.CMP_GE: x >= imm,
        OpCode.CMP_LT: x < imm, OpCode.CMP_LE: x <= imm,
        OpCode.CMP_EQ: x == imm, OpCode.CMP_NE: x != imm,
    }[insn.op]


def _apply_record_np(x: np.ndarray, records: np.ndarray,
                     insn: Instruction) -> np.ndarray:
    col = records[:, insn.imm]
    if insn.op == OpCode.LOAD:
        return col
    with np.errstate(over="ignore"):
        return (x * col).astype(x.dtype)   # MUL_FIELD wraps like MUL


def _hist_bin_np(x: np.ndarray, lo, hi, bins: int) -> tuple[np.ndarray, np.ndarray]:
    in_range = (x >= lo) & (x < hi)
    # use float64 bin math so int and float streams agree across tiers
    idx = np.floor((x.astype(np.float64) - lo) * bins / (hi - lo)).astype(np.int64)
    idx = np.clip(idx, 0, bins - 1)
    return idx, in_range


@dataclass
class OffloadResult:
    """What travels back over the link -- the whole point of the paper."""

    value: object                      # scalar, histogram array, or (values, count)
    bytes_returned: int
    pages_processed: int
    insns_executed: int
    exec_seconds: float
    compile_seconds: float = 0.0
    read_seconds: float = 0.0          # time inside device transfers
    cache_hits: int = 0                # compiled-executable cache hits
    cache_misses: int = 0


# ---------------------------------------------------------------------------
# tier "native": vectorized numpy — doubles as the semantic oracle for tests
# ---------------------------------------------------------------------------

def run_oracle(program: Program, data: np.ndarray) -> object:
    """Vectorized reference semantics over the whole (typed) zone contents."""
    x = np.asarray(data, dtype=np.dtype(program.input_dtype)).reshape(-1)
    records = None
    mask = np.ones(x.shape, dtype=bool)
    for insn in program.insns[:-1]:
        if insn.op == OpCode.FIELD:
            stride, index = insn.imm
            records = x.reshape(-1, stride)
            x = records[:, index]
            mask = np.ones(x.shape, dtype=bool)
        elif insn.op in RECORD_OPS:
            x = _apply_record_np(x, records, insn)
        elif insn.op in CMP_OPS:
            mask &= _apply_cmp_np(x, insn)
        else:
            x = _apply_alu_np(x, insn)
    term = program.terminal
    if term.op == OpCode.SELECT_REC:
        cap = program.select_capacity
        sel = records[mask]
        out = np.zeros((cap, records.shape[1]), records.dtype)
        n = min(sel.shape[0], cap)
        out[:n] = sel[:n]
        return out, np.int64(sel.shape[0])
    if term.op == OpCode.RED_COUNT:
        return np.int64(mask.sum())
    if term.op == OpCode.RED_SUM:
        widen = _SUM_WIDEN[x.dtype]
        return widen(x[mask].astype(widen).sum())
    if term.op in (OpCode.RED_MIN, OpCode.RED_MAX):
        ident = x.dtype.type(_minmax_identity(term.op, x.dtype))
        sel = x[mask]
        if sel.size == 0:
            return ident
        return sel.min() if term.op == OpCode.RED_MIN else sel.max()
    if term.op == OpCode.RED_HIST:
        lo, hi, bins = term.imm
        idx, in_range = _hist_bin_np(x, lo, hi, bins)
        return np.bincount(idx[mask & in_range], minlength=bins).astype(np.int64)
    if term.op == OpCode.SELECT:
        cap = program.select_capacity
        sel = x[mask]
        out = np.zeros(cap, dtype=x.dtype)
        n = min(sel.size, cap)
        out[:n] = sel[:n]
        return out, np.int64(sel.size)   # count reports ALL matches (truncation visible)
    raise AssertionError(term)


# ---------------------------------------------------------------------------
# tier "interp": stack-machine VM (paper's uBPF-without-JIT)
# ---------------------------------------------------------------------------

def interpret_program(
    program: Program,
    read_page: Callable[[int], np.ndarray],
    n_pages: int,
    page_elems: int,
) -> OffloadResult:
    """One instruction at a time, one page at a time, with per-access memory
    bounds checks -- deliberately mirrors the uBPF stack machine the paper
    benchmarks as its slow tier. ``read_page`` is the device's bounds-checked
    ``bpf_read`` hook."""
    dtype = np.dtype(program.input_dtype)
    term = program.terminal
    # accumulator init
    count = np.int64(0)
    acc_sum = _SUM_WIDEN[dtype](0)
    acc_mm = dtype.type(_minmax_identity(term.op, dtype)) \
        if term.op in (OpCode.RED_MIN, OpCode.RED_MAX) else None
    hist = np.zeros(term.imm[2], dtype=np.int64) if term.op == OpCode.RED_HIST else None
    sel_buf = np.zeros(program.select_capacity, dtype=dtype) \
        if term.op == OpCode.SELECT else None
    rec_stride = program.insns[0].imm[0] if (
        term.op == OpCode.SELECT_REC) else None
    rec_buf = np.zeros((program.select_capacity, rec_stride), dtype=dtype) \
        if term.op == OpCode.SELECT_REC else None
    sel_n = np.int64(0)

    insns_executed = 0
    t0 = time.perf_counter()
    for p in range(n_pages):
        page = np.asarray(read_page(p))
        # reinterpret in place (pages are block-aligned, so the typed view is
        # free); raw uint8 device reads and pre-typed test doubles both work
        x = page.reshape(-1).view(dtype) if page.dtype != dtype \
            else page.reshape(-1)
        # explicit bounds check per access (the uBPF interp overhead the
        # paper attributes its slow tier to)
        if x.size != page_elems:
            raise IndexError(
                f"page {p}: access of {x.size} elements outside page bound {page_elems}"
            )
        mask = np.ones(x.shape, dtype=bool)
        records = None
        for insn in program.insns[:-1]:
            insns_executed += 1
            if insn.op == OpCode.FIELD:
                stride, index = insn.imm
                if x.size % stride != 0 or index >= stride:  # bounds check
                    raise IndexError(f"FIELD access out of record bounds on page {p}")
                records = x.reshape(-1, stride)
                x = records[:, index]
                mask = np.ones(x.shape, dtype=bool)
            elif insn.op in RECORD_OPS:
                if records is None or insn.imm >= records.shape[1]:
                    raise IndexError(
                        f"{insn.op.value} access out of record bounds on "
                        f"page {p}")
                x = _apply_record_np(x, records, insn)
            elif insn.op in CMP_OPS:
                mask &= _apply_cmp_np(x, insn)
            else:
                x = _apply_alu_np(x, insn)
        insns_executed += 1  # the terminal
        if term.op == OpCode.RED_COUNT:
            count += mask.sum()
        elif term.op == OpCode.RED_SUM:
            acc_sum += x[mask].astype(acc_sum.dtype).sum()
        elif term.op == OpCode.RED_MIN:
            sel = x[mask]
            if sel.size:
                acc_mm = min(acc_mm, sel.min())
        elif term.op == OpCode.RED_MAX:
            sel = x[mask]
            if sel.size:
                acc_mm = max(acc_mm, sel.max())
        elif term.op == OpCode.RED_HIST:
            lo, hi, bins = term.imm
            idx, in_range = _hist_bin_np(x, lo, hi, bins)
            hist += np.bincount(idx[mask & in_range], minlength=bins).astype(np.int64)
        elif term.op == OpCode.SELECT:
            sel = x[mask]
            space = program.select_capacity - int(sel_n)
            if space > 0 and sel.size:
                take = min(space, sel.size)
                # bounds-checked write into the return buffer
                sel_buf[int(sel_n) : int(sel_n) + take] = sel[:take]
            sel_n += sel.size
        elif term.op == OpCode.SELECT_REC:
            sel = records[mask]
            space = program.select_capacity - int(sel_n)
            if space > 0 and sel.shape[0]:
                take = min(space, sel.shape[0])
                rec_buf[int(sel_n) : int(sel_n) + take] = sel[:take]
            sel_n += sel.shape[0]
    dt_exec = time.perf_counter() - t0

    if term.op == OpCode.RED_COUNT:
        value, nbytes = count, 8
    elif term.op == OpCode.RED_SUM:
        value, nbytes = acc_sum, 8
    elif term.op in (OpCode.RED_MIN, OpCode.RED_MAX):
        value, nbytes = acc_mm, dtype.itemsize
    elif term.op == OpCode.RED_HIST:
        value, nbytes = hist, hist.nbytes
    elif term.op == OpCode.SELECT_REC:
        value, nbytes = (rec_buf, sel_n), rec_buf.nbytes + 8
    else:
        value, nbytes = (sel_buf, sel_n), sel_buf.nbytes + 8
    return OffloadResult(value, nbytes, n_pages, insns_executed, dt_exec)


# ---------------------------------------------------------------------------
# tier "jit": XLA-compiled, streamed in tiles of pages (paper's uBPF-JIT)
# ---------------------------------------------------------------------------

# Bytes one step of the JIT scan reads, summed over a vmapped batch. Each
# step is one trip of an XLA while loop, whose fixed cost (about 1.7 us on a
# TPU v5e) a single 4 KiB page cannot pay for. On a v5e, over a 1077 MiB
# zone, a one-compare COUNT ran at 0.3% of the HBM roofline a page a step
# and 48% at 4 MiB. Steps of 8-32 MiB saved at most 1.1 ms of a call that
# spends 110 ms putting the zone into HBM; TPC-H Q6, bound by its int64
# product-sum and not by the loop, ran no faster (slower at 32 MiB) and
# compiled in 2.3-14 s instead of 0.5 s. At 4 MiB the array scan's
# vmapped batch of 2,048 chunks already fills a step with one page.
_SCAN_STEP_BYTES = 4 << 20


def pages_per_step(n_pages: int, page_bytes: int, batch: int = 1) -> int:
    """Pages one step of the JIT scan reads: the fewest whose bytes, over a
    vmapped batch of ``batch`` extents, reach ``_SCAN_STEP_BYTES``, and at
    most the ``n_pages`` of the extent (a short extent is one step)."""
    want = -(-_SCAN_STEP_BYTES // (page_bytes * batch))
    return max(1, min(n_pages, want))


@dataclass
class JittedProgram:
    fn: Callable                     # (pages[n_pages, page_elems]) -> result
    compile_seconds: float           # the paper's "JIT time" statistic
    n_pages: int
    page_elems: int
    program: Program
    pages_per_step: Optional[int] = None   # the JIT scan's tile; None: a kernel

    @property
    def steps(self) -> Optional[int]:
        """Steps the JIT scan takes over the extent: full tiles, and the
        remaining pages as one more (None for a Pallas kernel)."""
        if self.pages_per_step is None:
            return None
        return -(-self.n_pages // self.pages_per_step)

    def __call__(self, pages) -> object:
        # the executable was compiled under 64-bit mode; the call must run
        # under it too, or device_put canonicalizes int64/float64 zone pages
        # down to 32 bits and the input aval check rejects them
        with offload_x64():
            return self.fn(pages)

    @staticmethod
    def put(pages) -> jax.Array:
        """Start the host-to-HBM put of ``pages`` that a call would make
        itself, and return the device array without waiting for it."""
        with offload_x64():
            return jax.device_put(pages)


def named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``, which ``jax.jit`` gives the executable: the
    profiler's ``XLA Modules`` line then reads ``jit_<name>``."""
    def inner(*args):
        return fn(*args)
    inner.__name__ = inner.__qualname__ = name
    return inner


def _stream_mask_jnp(program: Program, x: jnp.ndarray):
    mask = jnp.ones(x.shape, dtype=bool)
    for insn in program.insns[:-1]:
        op, imm = insn.op, insn.imm
        if op == OpCode.FIELD:
            stride, index = imm
            records = x.reshape(-1, stride)
            x = records[:, index]
            mask = jnp.ones(x.shape, dtype=bool)
        elif op == OpCode.LOAD:
            x = records[:, imm]
        elif op == OpCode.MUL_FIELD:
            x = x * records[:, imm]
        elif op in CMP_OPS:
            imm_t = jnp.asarray(imm, dtype=x.dtype)
            mask &= {
                OpCode.CMP_GT: x > imm_t, OpCode.CMP_GE: x >= imm_t,
                OpCode.CMP_LT: x < imm_t, OpCode.CMP_LE: x <= imm_t,
                OpCode.CMP_EQ: x == imm_t, OpCode.CMP_NE: x != imm_t,
            }[op]
        elif op == OpCode.ABS:
            x = jnp.abs(x)
        elif op == OpCode.NEG:
            x = -x
        else:
            imm_t = jnp.asarray(imm, dtype=x.dtype)
            x = {
                OpCode.ADD: lambda: x + imm_t, OpCode.SUB: lambda: x - imm_t,
                OpCode.MUL: lambda: x * imm_t, OpCode.AND: lambda: x & imm_t,
                OpCode.OR: lambda: x | imm_t, OpCode.XOR: lambda: x ^ imm_t,
                OpCode.SHL: lambda: x << imm, OpCode.SHR: lambda: x >> imm,
                OpCode.MOD: lambda: x % imm_t,
            }[op]()
    return x, mask


def _build_program_runner(program: Program, batch: int = 1):
    """Build the tile-scanning ``run(pages)`` closure shared by the single
    (:func:`jit_program`) and chunk-batched (:func:`jit_program_batched`,
    which vmaps it over ``batch`` extents) compile paths.

    ``run`` steps over ``pages[n_pages, page_elems]`` a tile of
    :func:`pages_per_step` pages at a time, each a ``dynamic_slice`` of the
    input (nothing is copied or padded), and the last ``n_pages mod
    pages_per_step`` pages as one more step of their own shape. A step
    applies the program to its tile's elements in page order, so answers,
    SELECT order included, are those of a page-by-page scan."""
    dtype = np.dtype(program.input_dtype)
    term = program.terminal
    cap = program.select_capacity

    def init_carry():
        if term.op == OpCode.RED_COUNT:
            return jnp.zeros((), jnp.int64)
        if term.op == OpCode.RED_SUM:
            return jnp.zeros((), _SUM_WIDEN[dtype])
        if term.op in (OpCode.RED_MIN, OpCode.RED_MAX):
            return jnp.asarray(_minmax_identity(term.op, dtype), dtype)
        if term.op == OpCode.RED_HIST:
            return jnp.zeros((term.imm[2],), jnp.int64)
        if term.op == OpCode.SELECT:
            return (jnp.zeros((cap + 1,), dtype), jnp.zeros((), jnp.int64))
        if term.op == OpCode.SELECT_REC:
            stride = program.insns[0].imm[0]
            return (jnp.zeros((cap + 1, stride), dtype),
                    jnp.zeros((), jnp.int64))
        raise AssertionError(term)

    def step(carry, tile):
        # ``tile``: one page [page_elems] or a tile [k, page_elems]; every
        # op below reads it in row-major (page) order
        x, mask = _stream_mask_jnp(program, tile)
        if term.op == OpCode.RED_COUNT:
            return carry + mask.sum(dtype=jnp.int64), None
        if term.op == OpCode.RED_SUM:
            return carry + jnp.where(mask, x, 0).astype(carry.dtype).sum(), None
        if term.op == OpCode.RED_MIN:
            ident = jnp.asarray(_minmax_identity(term.op, dtype), dtype)
            return jnp.minimum(carry, jnp.where(mask, x, ident).min()), None
        if term.op == OpCode.RED_MAX:
            ident = jnp.asarray(_minmax_identity(term.op, dtype), dtype)
            return jnp.maximum(carry, jnp.where(mask, x, ident).max()), None
        if term.op == OpCode.RED_HIST:
            lo, hi, bins = term.imm
            in_range = (x >= lo) & (x < hi)
            idx = jnp.floor(
                (x.astype(jnp.float64) - lo) * bins / (hi - lo)
            ).astype(jnp.int64)
            idx = jnp.clip(idx, 0, bins - 1)
            upd = jnp.where(mask & in_range, 1, 0).astype(jnp.int64)
            return carry.at[idx].add(upd), None
        if term.op == OpCode.SELECT:
            buf, n = carry
            pos = n + jnp.cumsum(mask).reshape(mask.shape) - 1
            ok = mask & (pos < cap)
            # overflow writes land in the scratch slot [cap]
            buf = buf.at[jnp.where(ok, pos, cap)].set(x)
            return (buf, n + mask.sum(dtype=jnp.int64)), None
        if term.op == OpCode.SELECT_REC:
            buf, n = carry
            stride = program.insns[0].imm[0]
            records = tile.reshape(-1, stride)
            pos = n + jnp.cumsum(mask) - 1
            ok = mask & (pos < cap)
            buf = buf.at[jnp.where(ok, pos, cap)].set(records)
            return (buf, n + mask.sum(dtype=jnp.int64)), None
        raise AssertionError(term)

    def run(pages):
        n_pages, page_elems = pages.shape
        k = pages_per_step(n_pages, page_elems * dtype.itemsize, batch)
        n_tiles, tail = divmod(n_pages, k)
        if k == 1:
            # a page fills a step (a wide vmapped batch): lax.scan slices
            # its xs itself, the program a page-a-step scan always was
            carry, _ = jax.lax.scan(step, init_carry(), pages)
        else:
            def tile_step(i, carry):
                tile = jax.lax.dynamic_slice_in_dim(pages, i * k, k)
                return step(carry, tile)[0]
            carry = jax.lax.fori_loop(0, n_tiles, tile_step, init_carry())
        if tail:
            carry, _ = step(carry, pages[n_tiles * k:])
        if term.op in (OpCode.SELECT, OpCode.SELECT_REC):
            buf, n = carry
            return buf[:cap], n
        return carry

    return run


def jit_program(
    program: Program,
    n_pages: int,
    page_elems: int,
    *,
    donate: bool = False,
) -> JittedProgram:
    """Compile ``program`` to XLA. The compiled function scans the zone a
    tile of :func:`pages_per_step` pages at a time (a bounded working set,
    read in place from the extent) and carries only the reduction
    accumulator."""
    dtype = np.dtype(program.input_dtype)
    run = named(_build_program_runner(program), "zcsd_jit_scan")
    spec = jax.ShapeDtypeStruct((n_pages, page_elems), dtype)
    t0 = time.perf_counter()
    # int64 accumulators need 64-bit mode at *trace* time; scope it to the
    # offload compiler so the model stack keeps JAX's 32-bit defaults.
    with offload_x64():
        jitted = jax.jit(run, donate_argnums=(0,) if donate else ())
        compiled = jitted.lower(spec).compile()
    compile_seconds = time.perf_counter() - t0
    return JittedProgram(
        compiled, compile_seconds, n_pages, page_elems, program,
        pages_per_step(n_pages, page_elems * dtype.itemsize))


def jit_program_batched(
    program: Program,
    n_chunks: int,
    n_pages: int,
    page_elems: int,
) -> JittedProgram:
    """Compile ``program`` vmapped over a leading *chunk* axis.

    The array scheduler uses this to execute every same-shape shard of a
    striped offload in ONE XLA call: input ``[n_chunks, n_pages, page_elems]``,
    output a per-chunk result batch (e.g. ``[n_chunks]`` partial sums, or
    ``([n_chunks, cap], [n_chunks])`` for SELECT) that the combiner then
    re-reduces in logical stripe order."""
    dtype = np.dtype(program.input_dtype)
    run = named(jax.vmap(_build_program_runner(program, batch=n_chunks)),
                "zcsd_jit_scan_batched")
    spec = jax.ShapeDtypeStruct((n_chunks, n_pages, page_elems), dtype)
    t0 = time.perf_counter()
    with offload_x64():
        compiled = jax.jit(run).lower(spec).compile()
    compile_seconds = time.perf_counter() - t0
    return JittedProgram(
        compiled, compile_seconds, n_pages, page_elems, program,
        pages_per_step(n_pages, page_elems * dtype.itemsize, n_chunks))
