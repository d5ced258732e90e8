"""Reduces a ``jax.profiler`` trace of one window to device metrics.

The TPU's plane (``/device:TPU:<n>``) carries two lines the reduction reads:
``XLA Modules``, one event per executable run, and ``XLA Ops``, one event per
HLO op, where a ``while`` op's event spans the ops of its body. Host planes
carry the benchmark's own ``TraceAnnotation`` spans (``bench.*``) on the same
clock. From them:

* busy: the union of the intervals in which a module or an op ran, clipped to
  the window, averaged over the chips;
* kernel time: the union of the intervals of every op that is not a
  transfer, whatever implements the scan;
* the ops that took most self time (an op's duration less the ops nested in
  it);
* the idle gaps, each labelled by the ``bench.*`` annotation that covers most
  of it, or ``host`` where none does.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = ["DeviceWindow", "reduce_trace", "union_length", "gaps"]

WINDOW_ANNOTATION = "bench.window"
_TRANSFER = re.compile(
    r"^%?(copy-start|copy-done|send|send-done|recv|recv-done|infeed|outfeed)"
    r"(\.\d+)?$")


@dataclass
class DeviceWindow:
    chips: int
    window_s: float
    busy_s: float                 # averaged over chips
    kernel_s: float               # summed over chips
    ops: list = field(default_factory=list)    # [(name, self seconds)]
    gaps: list = field(default_factory=list)   # [(label, seconds)]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _short(name: str) -> str:
    return name.split(" = ", 1)[0].strip()


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def union_length(intervals) -> float:
    """Total length covered by ``intervals`` (pairs of start, end)."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps(intervals, lo, hi) -> list:
    """The stretches of ``[lo, hi)`` that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def _self_times(events) -> dict:
    """Per op name, its duration less that of the ops nested inside it."""
    acc: dict = {}
    stack: list = []                   # [name, start, end, nested time]

    def close(item):
        name, start, end, kids = item
        acc[name] = acc.get(name, 0.0) + max((end - start) - kids, 0.0)
        if stack:
            stack[-1][3] += end - start

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        stack.append([name, a, b, 0.0])
    while stack:
        close(stack.pop())
    return acc


def _label(gap, annotations) -> str:
    a, b = gap
    best, cover = "host", 0.0
    for name, s, e in annotations:
        c = min(b, e) - max(a, s)
        if c > cover:
            best, cover = name, c
    return best


def reduce_trace(path: str, *, window=None, top: int = 10) -> DeviceWindow:
    """Reduce the ``.xplane.pb`` at ``path``. The window is the benchmark's
    ``bench.window`` annotation, or ``window=(start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    annotations, chips = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [(_short(e.name), e.start_ns,
                            e.start_ns + e.duration_ns) for e in line.events]
            chips.append((mods, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        annotations.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
    if not chips:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    if window is None:
        spans = [(s, e) for n, s, e in annotations if n == WINDOW_ANNOTATION]
        if not spans:
            raise ValueError(f"{path}: no {WINDOW_ANNOTATION} annotation")
        window = spans[0]
    lo, hi = window
    others = [a for a in annotations if a[0] != WINDOW_ANNOTATION]
    busy, kernel, selfs, idle = 0.0, 0.0, {}, []
    for mods, ops in chips:
        iv = _clip(mods + [(a, b) for _, a, b in ops], lo, hi)
        busy += union_length(iv)
        kernel += union_length(_clip(
            [(a, b) for n, a, b in ops if not _TRANSFER.match(n)], lo, hi))
        for n, t in _self_times(
                [(n, max(a, lo), min(b, hi)) for n, a, b in ops
                 if b > lo and a < hi]).items():
            selfs[n] = selfs.get(n, 0.0) + t
        idle += [(_label(g, others), (g[1] - g[0]) * 1e-9)
                 for g in gaps(iv, lo, hi)]
    ops = sorted(selfs.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(idle, key=lambda g: -g[1])[:top]
    n = len(chips)
    return DeviceWindow(
        chips=n, window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
        kernel_s=kernel * 1e-9,
        ops=[(name, t * 1e-9) for name, t in ops], gaps=idle)
