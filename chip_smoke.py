#!/usr/bin/env python3
"""Smoke run of the offload path on one TPU chip.

Drives the system's main path once through its public entry points, at the
paper's sizes, and checks every answer against the numpy reference
(:func:`repro.core.run_oracle` or a direct numpy count):

* ``fig2``  -- the paper's Figure 2 scan: one 256 MiB zone of seeded int32 in
  ``[0, RAND_MAX)``, COUNT above ``RAND_MAX // 2`` through
  ``NvmCsd.nvm_cmd_bpf_run`` on the XLA (``jit``) and Pallas (``kernel``)
  tiers;
* ``array`` -- a raid0 ``StripedZoneArray`` of 4 members with the default
  stripe: a 1 GiB int32 zone and a 1 GiB float32 zone, each program through
  ``OffloadScheduler.run_and_fetch`` on the ``jit`` tier and, where the
  program is kernelizable, the ``kernel`` tier.

Run from the repository root, as ``python chip_smoke.py``. It needs a TPU:
without one it exits nonzero and prints no result. The times it prints are
smoke readings (one process, a few runs), not benchmark figures. On success
the last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
sys.path.insert(0, str(SRC))

RAND_MAX = 2**31 - 1
MIB = 1 << 20
BLOCK = 4096
SEED = 0
RUNS = 3                      # timed runs after the warm-up


class Mismatch(AssertionError):
    pass


def _die(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _reading(phase: str, tier: str, program: str, compile_s: float,
             walls: list[float]) -> None:
    print(f"smoke reading (not a benchmark figure) phase={phase} tier={tier} "
          f"program={program} compile_s={compile_s!r} "
          f"warm_wall_s={[float(w) for w in walls]!r}", flush=True)


def _timed(run, n: int):
    """``n`` calls of ``run()``; returns the results and wall seconds, each
    with the result already materialized on the host."""
    outs, walls = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        outs.append(run())
        walls.append(time.perf_counter() - t0)
    return outs, walls


def phase_fig2(zone_bytes: int = 256 * MIB, runs: int = RUNS) -> None:
    import numpy as np
    from repro.core import CsdTier, NvmCsd, filter_count
    from repro.zns import ZonedDevice

    dev = ZonedDevice(num_zones=1, zone_bytes=zone_bytes, block_bytes=BLOCK)
    data = np.random.default_rng(SEED).integers(
        0, RAND_MAX, zone_bytes // 4, dtype=np.int32)
    dev.zone_append(0, data)
    program = filter_count("int32", "gt", RAND_MAX // 2)
    want = int(np.count_nonzero(data > RAND_MAX // 2))
    csd = NvmCsd(dev)
    for tier in (CsdTier.JIT, CsdTier.KERNEL):
        outs, _ = _timed(lambda: csd.run_and_fetch(program, 0, tier=tier), 1)
        compile_s = outs[0][1].jit_seconds
        more, walls = _timed(
            lambda: csd.run_and_fetch(program, 0, tier=tier), runs)
        for got, stats in outs + more:
            if stats.tier != tier:
                raise Mismatch(f"fig2: ran on tier {stats.tier}, not {tier}")
            if int(got) != want:
                raise Mismatch(f"fig2 {tier}: count {int(got)} != {want}")
        _reading("fig2", tier, program.name, compile_s, walls)
    print(f"fig2: pass ({want} of {data.size} above RAND_MAX/2)", flush=True)


def _array_programs():
    from repro.core import (filter_count, filter_select, filter_sum,
                            histogram)
    from repro.core.programs import Instruction, OpCode, Program
    half = RAND_MAX // 2
    ints = [
        filter_count("int32", "gt", half),
        filter_sum("int32", "gt", half),
        Program("int32", (Instruction(OpCode.CMP_GT, half),
                          Instruction(OpCode.RED_MIN)), name="gt_min"),
        Program("int32", (Instruction(OpCode.CMP_LT, half),
                          Instruction(OpCode.RED_MAX)), name="lt_max"),
        # ~2000 matches per GiB: more than the capacity, so truncation shows
        filter_select("int32", "gt", RAND_MAX - 16_000, capacity=1024),
        histogram("int32", 0, RAND_MAX, 16),
    ]
    floats = [
        filter_sum("float32", "gt", 0.0),
        Program("float32", (Instruction(OpCode.CMP_GT, 0.0),
                            Instruction(OpCode.RED_MIN)), name="gt_fmin"),
        Program("float32", (Instruction(OpCode.RED_MAX),), name="fmax"),
    ]
    return ints, floats


def _check(program, got, want) -> None:
    """Integer terminals, MIN/MAX, SELECT and HIST exactly; float SUM to the
    tolerance the tier tests hold it to."""
    import numpy as np
    from repro.core.programs import OpCode
    if isinstance(want, tuple):
        vals, n = got
        if int(n) != int(want[1]) or not np.array_equal(np.asarray(vals),
                                                        want[0]):
            raise Mismatch(f"{program.name}: select differs from the oracle")
    elif (program.terminal.op == OpCode.RED_SUM
          and np.dtype(program.input_dtype).kind == "f"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, err_msg=program.name)
    elif not np.array_equal(np.asarray(got), np.asarray(want)):
        raise Mismatch(f"{program.name}: {got!r} != oracle {want!r}")


def phase_array(member_zone_bytes: int = 256 * MIB, members: int = 4,
                runs: int = RUNS) -> None:
    import numpy as np
    from repro.array import OffloadScheduler, StripedZoneArray
    from repro.core import CsdTier, run_oracle
    from repro.kernels.zone_filter.ops import kernelizable
    from repro.zns import ZonedDevice

    n = member_zone_bytes * members // 4
    rng = np.random.default_rng(SEED + 1)
    zones = [rng.integers(0, RAND_MAX, n, dtype=np.int32),
             rng.standard_normal(n, dtype=np.float32) * np.float32(100)]
    arr = StripedZoneArray([
        ZonedDevice(num_zones=2, zone_bytes=member_zone_bytes,
                    block_bytes=BLOCK) for _ in range(members)])
    for z, data in enumerate(zones):
        arr.zone_append(z, data)
    ints, floats = _array_programs()
    cases = [(0, p) for p in ints] + [(1, p) for p in floats]
    failed, float_sums = [], {}
    with OffloadScheduler(arr) as sched:
        for zone, program in cases:
            want = run_oracle(program, zones[zone])
            tiers = [CsdTier.JIT] + (
                [CsdTier.KERNEL] if kernelizable(program) else [])
            for tier in tiers:
                try:
                    run = lambda: sched.run_and_fetch(program, zone, tier=tier)
                    outs, _ = _timed(run, 1)
                    more, walls = _timed(run, runs)
                    for got, stats in outs + more:
                        if stats.tier != tier:
                            raise Mismatch(f"{program.name}: ran on tier "
                                           f"{stats.tier}, not {tier}")
                        if (tier == CsdTier.KERNEL
                                and stats.batched_chunks != stats.n_chunks):
                            raise Mismatch(
                                f"{program.name}: {stats.batched_chunks} of "
                                f"{stats.n_chunks} chunks ran batched")
                        _check(program, got, want)
                    if program is floats[0]:
                        float_sums[tier] = np.float64(outs[0][0])
                    _reading("array", tier, program.name,
                             outs[0][1].jit_seconds, walls)
                    print(f"array: {program.name} tier={tier} pass "
                          f"({outs[0][1].n_chunks} chunks)", flush=True)
                except Exception:
                    traceback.print_exc()
                    failed.append(f"{program.name}/{tier}")
    # a 1-member array over the same logical bytes must give the same float
    # SUM bit for bit (same stripe geometry => same chunk partials)
    one = StripedZoneArray([ZonedDevice(num_zones=1,
                                        zone_bytes=member_zone_bytes * members,
                                        block_bytes=BLOCK)])
    one.zone_append(0, zones[1])
    program = floats[0]
    with OffloadScheduler(one) as sched:
        for tier, wide in float_sums.items():
            got, _ = sched.run_and_fetch(program, 0, tier=tier)
            if np.float64(got) != wide:
                print(f"array: {program.name} tier={tier}: {members}-member "
                      f"{wide!r} != 1-member {np.float64(got)!r}",
                      file=sys.stderr, flush=True)
                failed.append(f"{program.name}/{tier}/width")
    if failed:
        raise Mismatch(f"array: failed {failed}")
    print("array: pass", flush=True)


def main() -> int:
    if not (SRC / "repro" / "runtime.py").is_file():
        _die(f"no repro package under {SRC}: run this from a checkout of "
             f"the repository")
    from repro.runtime import place_compile_cache
    cache_dir = place_compile_cache()
    import jax
    devices = jax.devices()
    d0 = devices[0]
    print(f"device platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"compile_cache={cache_dir}", flush=True)
    if d0.platform != "tpu":
        _die(f"needs a TPU, found {d0.platform}; the smoke run never falls "
             f"back to another backend")
    failed = []
    for name, phase in (("fig2", phase_fig2), ("array", phase_array)):
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        print(f"{name}: phase wall {time.perf_counter() - t0!r} s", flush=True)
    if failed:
        _die(f"failed phases: {failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
