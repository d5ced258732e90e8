"""The runtime decisions of repro.runtime: the Pallas interpret choice, the
64-bit offload scope, and where the persistent compile cache goes."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import runtime
from repro.core import CsdTier, NvmCsd, filter_count, run_oracle
from repro.kernels.zone_filter.kernel import _pick_block_pages
from repro.zns import ZonedDevice

REPO = Path(__file__).resolve().parents[1]


def test_pallas_interprets_on_the_cpu_backend():
    assert jax.default_backend() == "cpu"
    assert runtime.pallas_interpret() is True


@pytest.mark.parametrize("n_pages", [256, 520])
def test_kernel_tier_offload_on_cpu_matches_oracle(n_pages):
    """The kernel tier through NvmCsd, interpreted because the backend is
    the CPU; 520 pages leave a ragged last block."""
    dev = ZonedDevice(num_zones=1, zone_bytes=n_pages * 4096, block_bytes=4096)
    data = np.random.default_rng(n_pages).integers(
        0, 2**31 - 1, (n_pages, 1024), dtype=np.int32)
    dev.zone_append(0, data)
    program = filter_count("int32", "gt", 2**30)
    got, stats = NvmCsd(dev).run_and_fetch(program, 0, tier=CsdTier.KERNEL)
    assert stats.tier == CsdTier.KERNEL
    assert int(got) == int(run_oracle(program, data))


@pytest.mark.parametrize("block_pages", [1, 3, 8, 100, 512, 4096])
def test_block_pages_always_tile(block_pages):
    """Every block is all of the pages or a whole number of 8-row sublane
    tiles, so the TPU lowering accepts it for any extent."""
    for n_pages in [1, 3, 7, 8, 16, 100, 260, 513, 520, 65536, 65537]:
        bp = _pick_block_pages(block_pages, n_pages)
        assert bp == n_pages or (bp % 8 == 0 and bp < n_pages)
        assert bp <= max(block_pages, 8) or bp == n_pages


def test_offload_x64_scope_is_scoped():
    with runtime.offload_x64():
        assert jax.numpy.asarray(1).dtype == np.int64
    assert jax.numpy.asarray(1).dtype == np.int32


@pytest.fixture
def cache_config(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    yield monkeypatch
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(cache_config, tmp_path):
    cache_config.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_dir_in_checkout(cache_config):
    cache_config.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = runtime.place_compile_cache()
    second = runtime.place_compile_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_importing_repro_sets_no_cache():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO / "src")
    code = ("import jax, repro.core, repro.array, repro.kernels, "
            "repro.serve.kv_zones, repro.runtime; "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "None"
