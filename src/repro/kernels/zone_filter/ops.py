"""Public jit'd wrappers for the zone_filter kernel, including the bridge
from verified offload Programs (repro.core) to the Pallas tier."""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.programs import CMP_OPS, OpCode, Program
from repro.runtime import offload_x64
from repro.kernels.zone_filter.kernel import (
    filtered_reduce_pallas,
    filtered_reduce_pallas_batched,
)

__all__ = ["zone_filter_count", "zone_reduce", "run_program_kernel",
           "run_program_kernel_batched", "kernel_program",
           "kernel_program_batched", "KERNELIZABLE_TERMINALS", "kernelizable"]

# RED_SUM over ints is NOT kernelized: TPU has no i64 accumulator and f32
# accumulation would silently lose precision vs the verifier-promised i64
# semantics — those programs fall back to the XLA JIT tier.
KERNELIZABLE_TERMINALS = frozenset(
    {OpCode.RED_COUNT, OpCode.RED_SUM, OpCode.RED_MIN, OpCode.RED_MAX})

_TERM_KIND = {
    OpCode.RED_COUNT: "count", OpCode.RED_SUM: "sum",
    OpCode.RED_MIN: "min", OpCode.RED_MAX: "max",
}


def kernelizable(program: Program) -> bool:
    term = program.terminal.op
    if term not in KERNELIZABLE_TERMINALS:
        return False
    if term == OpCode.RED_SUM and np.dtype(program.input_dtype).kind != "f":
        return False
    if any(i.op == OpCode.FIELD for i in program.insns):
        return False  # projection changes block geometry; JIT tier handles it
    return True


def _program_transform(program: Program):
    """Trace the ALU/CMP chain into a fused (vals, mask) transform."""
    def transform(x):
        mask = jnp.ones(x.shape, bool)
        for insn in program.insns[:-1]:
            op, imm = insn.op, insn.imm
            if op in CMP_OPS:
                immt = jnp.asarray(imm, x.dtype)
                mask &= {
                    OpCode.CMP_GT: x > immt, OpCode.CMP_GE: x >= immt,
                    OpCode.CMP_LT: x < immt, OpCode.CMP_LE: x <= immt,
                    OpCode.CMP_EQ: x == immt, OpCode.CMP_NE: x != immt,
                }[op]
            elif op == OpCode.ABS:
                x = jnp.abs(x)
            elif op == OpCode.NEG:
                x = -x
            else:
                immt = jnp.asarray(imm, x.dtype)
                x = {
                    OpCode.ADD: lambda: x + immt, OpCode.SUB: lambda: x - immt,
                    OpCode.MUL: lambda: x * immt, OpCode.AND: lambda: x & immt,
                    OpCode.OR: lambda: x | immt, OpCode.XOR: lambda: x ^ immt,
                    OpCode.SHL: lambda: x << immt, OpCode.SHR: lambda: x >> immt,
                    OpCode.MOD: lambda: x % immt,
                }[op]()
        return x, mask
    return transform


@functools.partial(jax.jit, static_argnames=("threshold", "block_pages"))
def zone_filter_count(pages, threshold, *, block_pages: int = 512):
    """The paper's workload: count zone elements above threshold."""
    thr = threshold
    return filtered_reduce_pallas(
        pages, kind="count",
        transform=lambda x: (x, x > jnp.asarray(thr, x.dtype)),
        block_pages=block_pages)


@functools.partial(jax.jit, static_argnames=("kind", "threshold",
                                             "block_pages"))
def zone_reduce(pages, kind: str = "count", threshold=None, *,
                block_pages: int = 512):
    if threshold is None:
        transform = None
    else:
        thr = threshold
        transform = lambda x: (x, x > jnp.asarray(thr, x.dtype))
    return filtered_reduce_pallas(pages, kind=kind, transform=transform,
                                  block_pages=block_pages)


def _program_kernel(program: Program, kernel):
    """``kernel`` specialised to a verified, kernelizable ``program``."""
    if not kernelizable(program):
        raise ValueError(f"program {program.name} is not kernelizable")
    return functools.partial(kernel, kind=_TERM_KIND[program.terminal.op],
                             transform=_program_transform(program))


def run_program_kernel(program: Program, pages: np.ndarray):
    """Execute a verified Program on the Pallas tier (the CSD 'hardware
    backend'). Caller guarantees kernelizable(program).

    Convenience entry that re-traces per call; the CSD hot path goes through
    :func:`kernel_program` so the compiled executable lands in the shared
    :class:`~repro.core.cache.CompiledProgramCache`.
    """
    fn = jax.jit(_program_kernel(program, filtered_reduce_pallas))
    return fn(jnp.asarray(pages))


def run_program_kernel_batched(program: Program, pages: np.ndarray):
    """Chunk-batched Pallas execution: ``pages[n_chunks, n_pages, page_elems]``
    -> per-chunk reduced values ``[n_chunks]`` from ONE grid-batched kernel
    call (leading grid dimension over the chunk axis)."""
    fn = jax.jit(_program_kernel(program, filtered_reduce_pallas_batched))
    return fn(jnp.asarray(pages))


def _aot_compile(program: Program, kernel, shape: tuple[int, ...],
                 name: str):
    """AOT lower+compile of ``kernel`` for ``program`` at ``shape``, returned
    as a :class:`~repro.core.vm.JittedProgram` (so the kernel tier reports
    the paper's 'JIT time' and caches exactly like the XLA JIT tier). Traced
    under the offload 64-bit scope like the XLA JIT tier so int64/float64
    zone dtypes keep their verified semantics."""
    # local: keep the import DAG one-way
    from repro.core.vm import JittedProgram, named
    run = named(_program_kernel(program, kernel), name)
    spec = jax.ShapeDtypeStruct(shape, np.dtype(program.input_dtype))
    t0 = time.perf_counter()
    with offload_x64():
        compiled = jax.jit(run).lower(spec).compile()
    return JittedProgram(compiled, time.perf_counter() - t0, shape[-2],
                         shape[-1], program)


def kernel_program(program: Program, n_pages: int, page_elems: int):
    """Compile a verified Program to a shaped Pallas executable."""
    return _aot_compile(program, filtered_reduce_pallas,
                        (n_pages, page_elems), "zcsd_kernel_scan")


def kernel_program_batched(program: Program, n_chunks: int, n_pages: int,
                           page_elems: int):
    """Compile the chunk-batched Pallas kernel for a fixed
    ``[n_chunks, n_pages, page_elems]`` geometry (the scheduler's striped
    fan-out shape)."""
    return _aot_compile(program, filtered_reduce_pallas_batched,
                        (n_chunks, n_pages, page_elems),
                        "zcsd_kernel_scan_batched")
