"""The trace reduction on a small trace recorded on a TPU v5e: 40 short
offloads through ``OffloadScheduler`` in two ``bench.call`` annotations."""
import numpy as np
import pytest

from conftest import HERE

import devtrace

TRACE = HERE / "data" / "short_offloads.xplane.pb"


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(TRACE))


def _device_lines(pd):
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    return lines["XLA Modules"], lines["XLA Ops"]


def _window(pd):
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    calls = [e for ln in host.lines for e in ln.events
             if e.name == "bench.call"]
    return (min(e.start_ns for e in calls),
            max(e.start_ns + e.duration_ns for e in calls))


def test_union_and_gaps_are_complements():
    iv = [(0, 4), (2, 6), (10, 12), (11, 11.5), (20, 30)]
    assert devtrace.union_length(iv) == 6 + 2 + 10
    assert devtrace.gaps(iv, 0, 25) == [(6, 10), (12, 20)]
    assert devtrace.gaps([], 0, 5) == [(0, 5)]


def test_busy_kernel_idle_and_ops(pd):
    lo, hi = _window(pd)
    dw = devtrace.reduce_trace(str(TRACE), window=(lo, hi))
    mods, ops = _device_lines(pd)
    # independently, on a 1 us grid: a grid point is busy when a module or
    # an op covers it
    iv = sorted((e.start_ns, e.start_ns + e.duration_ns) for e in mods + ops)
    starts = np.array([a for a, _ in iv])
    reach = np.maximum.accumulate(np.array([b for _, b in iv]))
    grid = np.arange(lo, hi, 1000.0)
    k = np.searchsorted(starts, grid, side="right") - 1
    covered = int(((k >= 0) & (reach[np.maximum(k, 0)] > grid)).sum())
    assert dw.busy_s == pytest.approx(covered * 1e-6, rel=0.05)
    assert dw.chips == 1
    assert dw.window_s == pytest.approx((hi - lo) * 1e-9)
    assert 0 < dw.kernel_s <= dw.busy_s
    # 40 offloads of a few microseconds of device time in ~150 ms
    assert 0.95 < dw.idle_share < 1.0
    names = [n for n, _ in dw.ops]
    assert "%while.4" in names
    self_total = sum(t for _, t in dw.ops)
    assert self_total <= dw.kernel_s * 1.0001
    assert all(label == "bench.call" or label == "host"
               for label, _ in dw.gaps)
    assert max(s for _, s in dw.gaps) < dw.window_s


def test_roofline_share_of_the_recorded_offloads(pd):
    lo, hi = _window(pd)
    dw = devtrace.reduce_trace(str(TRACE), window=(lo, hi))
    # each short offload scanned 20 blocks of 4 KiB; 40 of them
    nbytes = 40 * 20 * 4096
    share = nbytes / 819e9 / dw.kernel_s * 100
    assert 0 < share <= 100


def test_a_trace_without_the_window_annotation_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        devtrace.reduce_trace(str(TRACE))
