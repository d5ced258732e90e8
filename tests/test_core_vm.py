"""Offload VM semantics: all execution tiers agree with the numpy oracle,
and the verifier rejects exactly the unsafe programs."""
import jax
import numpy as np
import pytest

from repro.core import (
    CsdTier,
    Instruction,
    NvmCsd,
    OpCode,
    Program,
    VerifyError,
    field_reduce,
    filter_count,
    filter_select,
    filter_sum,
    histogram,
    interpret_program,
    jit_program,
    run_oracle,
    verify_program,
)
from repro.core import vm
from repro.core.vm import jit_program_batched, pages_per_step
from repro.zns import ZonedDevice

RNG = np.random.default_rng(42)


def make_zone_data(n_pages=8, page_elems=1024, dtype=np.int32, seed=0):
    rng = np.random.default_rng(seed)
    if np.issubdtype(np.dtype(dtype), np.integer):
        info = np.iinfo(dtype)
        return rng.integers(info.min // 2, info.max // 2,
                            (n_pages, page_elems)).astype(dtype)
    return rng.standard_normal((n_pages, page_elems)).astype(dtype) * 100


def run_all_tiers(program, data):
    """Run on oracle / interpreter / XLA-JIT; return the three results."""
    n_pages, page_elems = data.shape
    oracle = run_oracle(program, data)
    interp = interpret_program(
        program, lambda p: data[p], n_pages, page_elems
    ).value
    jp = jit_program(program, n_pages, page_elems)
    jit = jp(data)
    return oracle, interp, jit


PROGRAMS = [
    filter_count("int32", "gt", 2**30),            # the paper's Fig.2 workload
    filter_count("int32", "le", 0),
    filter_sum("int32", "gt", 0),
    filter_sum("float32", "lt", 0.0),
    Program("int32", (Instruction(OpCode.ABS), Instruction(OpCode.RED_MAX))),
    Program("int32", (Instruction(OpCode.RED_MIN),)),
    Program("int32", (Instruction(OpCode.AND, 0xFF), Instruction(OpCode.CMP_EQ, 7),
                      Instruction(OpCode.RED_COUNT)), name="masked_eq"),
    Program("int32", (Instruction(OpCode.SHR, 8), Instruction(OpCode.CMP_GT, 100),
                      Instruction(OpCode.RED_SUM)), name="shift_sum"),
    Program("float32", (Instruction(OpCode.MUL, 2.0), Instruction(OpCode.CMP_GE, 10.0),
                        Instruction(OpCode.RED_COUNT)), name="scaled_count"),
    histogram("int32", -(2**30), 2**30, 64),
    field_reduce("int32", stride=4, index=2, kind="sum", cmp="gt", threshold=0),
    field_reduce("int32", stride=8, index=0, kind="max"),
]


@pytest.mark.parametrize("program", PROGRAMS, ids=lambda p: p.name)
def test_tiers_agree(program):
    data = make_zone_data(dtype=np.dtype(program.input_dtype))
    oracle, interp, jit = run_all_tiers(program, data)
    np.testing.assert_allclose(np.asarray(interp), np.asarray(oracle), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(jit), np.asarray(oracle), rtol=1e-6)


def test_select_tiers_agree():
    program = filter_select("int32", "gt", 2**29, capacity=16384)
    data = make_zone_data()
    (ov, on), (iv, in_), (jv, jn) = run_all_tiers(program, data)
    assert on == in_ == int(jn)
    n = min(int(on), 16384)
    np.testing.assert_array_equal(iv[:n], ov[:n])
    np.testing.assert_array_equal(np.asarray(jv)[:n], ov[:n])


def test_select_overflow_reports_true_count():
    program = filter_select("int32", "ge", np.iinfo(np.int32).min, capacity=8)
    data = make_zone_data(n_pages=2, page_elems=64)
    (_, on), (_, in_), (_, jn) = run_all_tiers(program, data)
    assert on == in_ == int(jn) == 128  # all match; capacity 8 << 128


def test_empty_min_returns_identity():
    program = Program("int32", (Instruction(OpCode.CMP_GT, np.iinfo(np.int32).max - 1),
                                Instruction(OpCode.RED_MIN)))
    data = make_zone_data(n_pages=2, page_elems=128)
    oracle, interp, jit = run_all_tiers(program, data)
    assert oracle == interp == int(jit) == np.iinfo(np.int32).max


# ------------------------------------------------------- JIT scan tiling

STEP = 4                     # pages a tile, with the step constant made small
TILE_PAGE_ELEMS = 128


def assert_same_answer(got, want, program):
    if isinstance(want, tuple):          # SELECT: (buffer, count of matches)
        np.testing.assert_array_equal(np.asarray(got[0]), want[0])
        assert int(got[1]) == int(want[1])
    elif program.terminal.op == OpCode.RED_SUM and np.issubdtype(
            np.asarray(want).dtype, np.floating):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(got), want)


def run_tiled(monkeypatch, program, data, batched):
    """The JIT tier over ``data`` ([n_pages, page_elems], or a leading chunk
    axis when ``batched``) with a tile of ``STEP`` pages; returns the
    executable and one answer per extent."""
    *lead, n_pages, page_elems = data.shape
    batch = lead[0] if batched else 1
    monkeypatch.setattr(vm, "_SCAN_STEP_BYTES",
                        STEP * page_elems * data.itemsize * batch)
    if batched:
        jp = jit_program_batched(program, batch, n_pages, page_elems)
        out = jp(data)
        return jp, [jax.tree.map(lambda a, c=c: a[c], out)
                    for c in range(batch)]
    jp = jit_program(program, n_pages, page_elems)
    return jp, [jp(data)]


# capacity 300 of about 256 matches a tile: the buffer fills in the second
TILED_PROGRAMS = PROGRAMS + [filter_select("int32", "gt", 0, capacity=300)]


@pytest.mark.parametrize("batched", [False, True], ids=["single", "batched"])
@pytest.mark.parametrize("n_pages", [1, STEP - 1, STEP, STEP + 1, 2 * STEP + 3])
@pytest.mark.parametrize("program", TILED_PROGRAMS, ids=lambda p: p.name)
def test_tiled_jit_scan_matches_oracle(monkeypatch, program, n_pages, batched):
    """Full tiles, a tail step of its own shape, and an extent shorter than
    one tile all give the page-by-page answer, per chunk when batched."""
    dtype = np.dtype(program.input_dtype)
    data = np.stack([make_zone_data(n_pages, TILE_PAGE_ELEMS, dtype, seed=s)
                     for s in range(2 if batched else 1)])
    jp, got = run_tiled(monkeypatch, program, data if batched else data[0],
                        batched)
    assert jp.pages_per_step == min(STEP, n_pages)
    assert jp.steps == -(-n_pages // min(STEP, n_pages))
    for chunk, answer in zip(data, got):
        assert_same_answer(answer, run_oracle(program, chunk), program)


PAGE_BYTES = 4096


@pytest.mark.parametrize("n_pages", [275_712, 187_538],
                         ids=["fig2-zone", "q6-lineitem"])
def test_whole_zone_extents_step_in_tiles_with_a_tail(n_pages):
    k = pages_per_step(n_pages, PAGE_BYTES)
    assert 1 < k < n_pages
    # the fewest pages whose bytes reach the step constant
    assert k * PAGE_BYTES >= vm._SCAN_STEP_BYTES > (k - 1) * PAGE_BYTES
    assert n_pages % k                    # the last step is a tail
    steps = -(-n_pages // k)
    assert steps == n_pages // k + 1
    jp = vm.JittedProgram(None, 0.0, n_pages, 1024, PROGRAMS[0], k)
    assert jp.steps == steps


def test_array_scan_batch_keeps_one_page_a_step():
    """2,048 chunks of 16 pages: a page over the batch is already 8 MiB."""
    assert pages_per_step(16, PAGE_BYTES, batch=2048) == 1


@pytest.mark.parametrize("n_pages", range(1, 17))
def test_short_extent_is_one_step(n_pages):
    assert pages_per_step(n_pages, PAGE_BYTES) == n_pages


# ------------------------------------------------------------------ verifier

def test_verifier_accepts_fig2_program():
    n = verify_program(filter_count("int32", "gt", 2**30),
                       page_elems=1024, n_pages=65536)
    assert n == 2 * 65536  # proven dynamic bound


@pytest.mark.parametrize("bad, msg", [
    (Program("int8", (Instruction(OpCode.RED_COUNT),)), "unsupported dtype"),
    (Program("int32", ()), "empty"),
    (Program("int32", (Instruction(OpCode.CMP_GT, 0),)), "not a terminal"),
    (Program("int32", (Instruction(OpCode.RED_COUNT), Instruction(OpCode.CMP_GT, 0),
                       Instruction(OpCode.RED_COUNT))), "not last"),
    (Program("float32", (Instruction(OpCode.AND, 3), Instruction(OpCode.RED_COUNT))),
     "bitwise op on non-integer"),
    (Program("int32", (Instruction(OpCode.SHL, 99), Instruction(OpCode.RED_COUNT))),
     "shift amount"),
    (Program("int32", (Instruction(OpCode.MOD, 0), Instruction(OpCode.RED_COUNT))),
     "modulo by zero"),
    (Program("int32", (Instruction(OpCode.CMP_GT, 2**40), Instruction(OpCode.RED_COUNT))),
     "out of int32 range"),
    (Program("int32", (Instruction(OpCode.RED_HIST, (5, 5, 16)),)), "empty histogram"),
    (Program("int32", (Instruction(OpCode.RED_HIST, (0, 10, 0)),)), "bins"),
    (Program("int32", (Instruction(OpCode.SELECT),)), "select_capacity"),
    (Program("int32", (Instruction(OpCode.CMP_GT, 0), Instruction(OpCode.FIELD, (4, 0)),
                       Instruction(OpCode.RED_COUNT))), "first instruction"),
    (Program("int32", (Instruction(OpCode.FIELD, (3, 1)), Instruction(OpCode.RED_COUNT))),
     "does not divide"),
    (Program("int32", (Instruction(OpCode.FIELD, (4, 9)), Instruction(OpCode.RED_COUNT))),
     "invalid FIELD"),
])
def test_verifier_rejects(bad, msg):
    with pytest.raises(VerifyError, match=msg):
        verify_program(bad, page_elems=1024, n_pages=16)


def test_verifier_dynamic_budget():
    from repro.core.verifier import VerifierLimits
    prog = filter_count("int32", "gt", 0)
    with pytest.raises(VerifyError, match="dynamic instruction bound"):
        verify_program(prog, page_elems=1024, n_pages=10**9,
                       limits=VerifierLimits(max_dynamic_insns=10**6))


# ------------------------------------------------------------------ NvmCsd

@pytest.fixture
def csd():
    dev = ZonedDevice(num_zones=2, zone_bytes=1024 * 1024, block_bytes=4096)
    data = make_zone_data(n_pages=256, page_elems=1024, seed=7)
    dev.zone_append(0, data)
    return NvmCsd(dev), data


def test_csd_run_matches_oracle_all_tiers(csd):
    dev_csd, data = csd
    program = filter_count("int32", "gt", 2**30)
    expected = run_oracle(program, data)
    for tier in (CsdTier.INTERP, CsdTier.JIT):
        stats = dev_csd.nvm_cmd_bpf_run(program, 0, tier=tier)
        assert int(dev_csd.nvm_cmd_bpf_result()) == int(expected)
        assert stats.pages == 256
        assert stats.bytes_read == 256 * 4096
        assert stats.bytes_returned <= 8
        assert stats.movement_saved_bytes == 256 * 4096 - stats.bytes_returned
        assert stats.insns_verified == 2 * 256


def test_csd_rejects_unwritten_extent(csd):
    dev_csd, _ = csd
    program = filter_count("int32", "gt", 0)
    with pytest.raises(VerifyError, match="write pointer"):
        dev_csd.nvm_cmd_bpf_run(program, 0, n_blocks=512)  # only 256 written
    with pytest.raises(VerifyError):
        dev_csd.nvm_cmd_bpf_run(program, 1)  # zone 1 empty


def test_csd_jit_cache_reports_compile_once(csd):
    dev_csd, _ = csd
    program = filter_sum("int32", "gt", 0)
    s1 = dev_csd.nvm_cmd_bpf_run(program, 0, tier=CsdTier.JIT)
    s2 = dev_csd.nvm_cmd_bpf_run(program, 0, tier=CsdTier.JIT)
    assert s1.jit_seconds > 0.0       # paper's "JIT time" statistic
    assert s2.jit_seconds == 0.0      # cached


def test_csd_async(csd):
    dev_csd, data = csd
    program = filter_count("int32", "gt", 0)
    fut = dev_csd.nvm_cmd_bpf_run_async(program, 0, tier=CsdTier.JIT)
    stats = fut.result(timeout=60)
    assert stats.pages == 256
    assert int(dev_csd.nvm_cmd_bpf_result()) == int(run_oracle(program, data))


def test_csd_oracle_path(csd):
    dev_csd, data = csd
    program = histogram("int32", -(2**30), 2**30, 32)
    got, _ = dev_csd.run_and_fetch(program, 0, tier=CsdTier.JIT)
    np.testing.assert_array_equal(np.asarray(got), dev_csd.oracle(program, 0))
