"""Compile the offload path's kernels and runners for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX lowers and compiles each
program for a chip that is described, not attached, so a block shape or a
layout the chip's compiler refuses fails here instead of on the chip. The
topology is described inside a module fixture (never at import), and the
persistent compile cache is off while these tests run, since a compile for
a described chip cannot be read back.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import filter_count, filter_sum
from repro.core import vm
from repro.core.programs import Instruction, OpCode, Program, tpch_q6
from repro.core.vm import _build_program_runner
from repro.kernels.paged_attn.kernel import paged_attention_pallas
from repro.kernels.zone_filter import ops as zf_ops
from repro.kernels.zone_filter.kernel import (
    filtered_reduce_pallas,
    filtered_reduce_pallas_batched,
)
from repro.runtime import offload_x64

RAND_MAX = 2**31 - 1
PAGE = 1024                     # int32/float32 elements in a 4 KiB page


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental import topologies
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    """Lower and compile ``fn`` for the described chip, under the offload
    64-bit scope the hot path traces in; returns the HLO text."""
    with offload_x64():
        return jax.jit(fn).lower(*shapes).compile().as_text()


def _kernel(program, kernel):
    """The hot path's kernel for ``program``, forced to compile."""
    return functools.partial(zf_ops._program_kernel(program, kernel),
                             interpret=False)


FIG2_PROGRAMS = [
    filter_count("int32", "gt", RAND_MAX // 2),
    Program("int32", (Instruction(OpCode.CMP_LT, 500),
                      Instruction(OpCode.RED_MIN)), name="lt_min"),
    Program("int32", (Instruction(OpCode.ABS), Instruction(OpCode.RED_MAX)),
            name="abs_max"),
    filter_sum("float32", "gt", 0.0),
]


@pytest.mark.parametrize("program", FIG2_PROGRAMS, ids=lambda p: p.name)
def test_zone_filter_compiles_at_fig2_shape(one_chip, program):
    """One 256 MiB zone of 4 KiB pages: the paper's Figure 2 extent."""
    spec = jax.ShapeDtypeStruct((65536, PAGE), program.input_dtype,
                                sharding=one_chip)
    hlo = _compile(_kernel(program, filtered_reduce_pallas), spec)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n_chunks", [2, 8])
def test_zone_filter_batched_compiles_at_stripe_shape(one_chip, n_chunks):
    """The scheduler's batch: 16-page chunks (default ``stripe_blocks``)."""
    spec = jax.ShapeDtypeStruct((n_chunks, 16, PAGE), jnp.int32,
                                sharding=one_chip)
    program = filter_count("int32", "gt", 0)
    hlo = _compile(_kernel(program, filtered_reduce_pallas_batched), spec)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n_pages", [520, 100, 3])
def test_zone_filter_compiles_for_untidy_page_counts(one_chip, n_pages):
    """Page counts that are not a power of two: a ragged last block (520),
    one block of all pages (100), and fewer pages than a sublane tile (3)."""
    spec = jax.ShapeDtypeStruct((n_pages, PAGE), jnp.int32, sharding=one_chip)
    hlo = _compile(functools.partial(filtered_reduce_pallas, kind="max",
                                     interpret=False), spec)
    assert "tpu_custom_call" in hlo


def test_paged_attention_compiles_at_granite_widths(one_chip):
    """granite-8b decode widths: 32 query heads, 8 KV heads, head_dim 128,
    over 256 zones of 128 tokens in bf16."""
    B, H, KV, hd, NZ, ZL, MZ = 8, 32, 8, 128, 256, 128, 32
    shapes = [
        jax.ShapeDtypeStruct((B, H, hd), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((NZ, ZL, KV, hd), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((NZ, ZL, KV, hd), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((B, MZ), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip),
    ]
    hlo = jax.jit(functools.partial(paged_attention_pallas, interpret=False)
                  ).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in hlo


def _compile_in_place(run, n_pages, one_chip):
    """Compile the single JIT runner over ``n_pages`` 4 KiB pages and check
    that it reads the extent where it lies: the scratch it asks for stays
    under one step's bytes, so no copy or padded copy of the extent."""
    spec = jax.ShapeDtypeStruct((n_pages, PAGE), jnp.int32, sharding=one_chip)
    with offload_x64():
        compiled = jax.jit(run).lower(spec).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < vm._SCAN_STEP_BYTES


@pytest.mark.parametrize("batched", [False, True], ids=["single", "vmapped"])
def test_count_jit_runner_compiles(one_chip, batched):
    """The XLA JIT tier's scan with its int64 COUNT carry: over fig2's
    1077 MiB zone in tiles of pages and a tail step, and vmapped."""
    run = _build_program_runner(filter_count("int32", "gt", RAND_MAX // 2))
    if batched:
        spec = jax.ShapeDtypeStruct((8, 16, PAGE), jnp.int32,
                                    sharding=one_chip)
        _compile(jax.vmap(run), spec)
    else:
        assert 275_712 % vm.pages_per_step(275_712, 4 * PAGE)   # a tail
        _compile_in_place(run, 275_712, one_chip)


def test_q6_jit_runner_compiles_with_its_tail(one_chip):
    """TPC-H Q6 over SF 1 ``lineitem`` (187,538 pages of 32 records): the
    record tiles and the tail step lower for the chip."""
    q6 = tpch_q6(32, shipdate=8, discount=6, quantity=4, extendedprice=5,
                 date_lo=8766, date_hi=9131, disc_lo=5, disc_hi=7, qty_lt=24)
    assert 187_538 % vm.pages_per_step(187_538, 4 * PAGE)
    _compile_in_place(_build_program_runner(q6), 187_538, one_chip)
