"""Paper Figure 2 reproduction: integer-filter offload across execution tiers.

Workload (faithful to §4): fill one zone with random int32s, count those
above RAND_MAX/2 (~50% selectivity), processing at page (4 KiB) granularity.
Scenarios:

  1. native   — host reads the zone and filters with vectorized numpy
                (the paper's "SPDK without computational capabilities");
  2. interp   — ZCSD uBPF-analogue stack machine, one instruction at a time,
                per-access bounds checks (paper scenario 2);
  3. jit      — ZCSD with the program JIT-compiled (XLA), streamed in tiles of pages
                (paper scenario 3; 'JIT time' reported separately);
  4. kernel   — Pallas zone-filter kernel (interpret mode on CPU) — the
                additional hardware-backend tier the paper lists as ongoing
                work.

Reported per scenario: init+fill seconds, filter seconds, JIT seconds, and
bytes moved to the host. The paper's key claims to check: JIT within ~1% of
native (we report the measured gap), interpreter slowest by a wide margin.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from repro.core import CsdTier, NvmCsd, filter_count
from repro.zns import ZonedDevice

RAND_MAX = 2**31 - 1


@dataclass
class Scenario:
    name: str
    fill_seconds: float
    filter_seconds: float
    jit_seconds: float
    bytes_to_host: int
    result: int


def run_figure2(zone_mib: int = 256, runs: int = 5, include_interp: bool = True,
                seed: int = 0) -> list[Scenario]:
    zone_bytes = zone_mib * 1024 * 1024
    n_ints = zone_bytes // 4
    program = filter_count("int32", "gt", RAND_MAX // 2)

    t0 = time.perf_counter()
    dev = ZonedDevice(num_zones=1, zone_bytes=zone_bytes, block_bytes=4096)
    rng = np.random.default_rng(seed)
    data = rng.integers(0, RAND_MAX, n_ints, dtype=np.int32)
    dev.zone_append(0, data)
    fill_seconds = time.perf_counter() - t0
    expected = int((data > RAND_MAX // 2).sum())

    out: list[Scenario] = []

    # 1. native (SPDK-style): read whole zone to host, numpy filter
    times = []
    for _ in range(runs):
        t = time.perf_counter()
        raw = dev.read_zone(0)          # the whole zone crosses the link
        host = raw.view(np.int32)       # retype in place, no second copy
        res = int((host > RAND_MAX // 2).sum())
        times.append(time.perf_counter() - t)
    assert res == expected
    out.append(Scenario("native-host", fill_seconds, float(np.mean(times)),
                        0.0, zone_bytes, res))

    csd = NvmCsd(dev)

    # 2. interp
    if include_interp:
        t = time.perf_counter()
        stats = csd.nvm_cmd_bpf_run(program, 0, tier=CsdTier.INTERP)
        dt = time.perf_counter() - t
        res = int(csd.nvm_cmd_bpf_result())
        assert res == expected
        out.append(Scenario("zcsd-interp", fill_seconds, dt, 0.0,
                            stats.bytes_returned, res))

    # 3. jit (first call pays compile; steady-state measured after)
    stats = csd.nvm_cmd_bpf_run(program, 0, tier=CsdTier.JIT)
    jit_seconds = stats.jit_seconds
    times = []
    for _ in range(runs):
        t = time.perf_counter()
        stats = csd.nvm_cmd_bpf_run(program, 0, tier=CsdTier.JIT)
        times.append(time.perf_counter() - t)
    res = int(csd.nvm_cmd_bpf_result())
    assert res == expected
    out.append(Scenario("zcsd-jit", fill_seconds, float(np.mean(times)),
                        jit_seconds, stats.bytes_returned, res))

    # 4. kernel (Pallas, interpret mode on CPU; first call compiles)
    csd.nvm_cmd_bpf_run(program, 0, tier=CsdTier.KERNEL)
    times = []
    for _ in range(max(runs // 2, 1)):
        t = time.perf_counter()
        stats = csd.nvm_cmd_bpf_run(program, 0, tier=CsdTier.KERNEL)
        times.append(time.perf_counter() - t)
    res = int(csd.nvm_cmd_bpf_result())
    assert res == expected
    out.append(Scenario("zcsd-pallas(interp)", fill_seconds,
                        float(np.mean(times)), 0.0, stats.bytes_returned, res))
    return out


def main(zone_mib: int = 32, runs: int = 3) -> list[str]:
    rows = []
    scenarios = run_figure2(zone_mib=zone_mib, runs=runs)
    native = scenarios[0].filter_seconds
    for s in scenarios:
        rows.append(
            f"fig2_{s.name},{s.filter_seconds * 1e6:.0f},"
            f"vs_native={s.filter_seconds / native:.2f}x;"
            f"jit_us={s.jit_seconds * 1e6:.0f};bytes_to_host={s.bytes_to_host}"
        )
    return rows


if __name__ == "__main__":
    for r in main(zone_mib=256, runs=5):
        print(r)
