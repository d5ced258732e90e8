"""Span tracing on wall time *and* reactor virtual time.

The reactor emulates device latency on a virtual timeline (``io_busy_until``
deadlines measured on ``time.monotonic()``), while the Python host threads —
dispatcher, gather pool, reactor pump — burn real wall time on the same
clock. A profile of the array fan-out is only legible if both kinds of
activity land on ONE timeline, so every event here carries
``time.monotonic()`` timestamps: host spans sample the clock around their
body; device-side "virtual" events are emitted post-hoc from the claimed
``(start, service)`` windows via :func:`event_complete`.

Design constraints from the hot path:

  * **near-zero disabled cost** — ``span()`` checks one module-level bool
    and returns a shared no-op singleton whose ``__enter__``/``__exit__``
    do nothing; no allocation, no lock, no clock read.
  * **lock-light enabled path** — each thread appends into its own
    preallocated ring buffer (a plain-list ring; the only global lock is
    taken once per thread at buffer registration). Overflow overwrites the
    oldest events and counts drops — tracing must never stall the reactor.
  * **nesting without frames** — a contextvar stack carries the parent
    span's tags, so a ``stage.read_wait`` span inside ``offload.execute``
    inherits tenant/device tags it never set; contextvars also follow the
    code into coroutine-style callbacks better than thread-locals would.
    Every event gets an ``id`` and its enclosing span's id as ``parent``,
    so a job handed to another thread in a copy of the submitter's context
    (the gather pool does this while tracing) still names what caused it.
  * **one clock with the device** — while tracing, each span also opens a
    ``jax.profiler.TraceAnnotation`` of its name, with its tags, id and
    parent as arguments, on the same thread. Under a ``jax.profiler``
    trace the program's spans then lie on the host plane of the same
    ``.xplane.pb`` as the device's ops, on its clock. The disabled path
    never builds one and never imports jax.

Export is Chrome ``trace_event`` JSON (``{"traceEvents": [...]}``) with
complete ("ph": "X") events: load it in Perfetto / chrome://tracing. Host
threads render as pid 1 (one row per thread); device virtual tracks as
pid 2 (one row per ``track=`` name, e.g. ``dev0/zone3``).
"""
from __future__ import annotations

import itertools
import json
import threading
import time
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Optional

__all__ = [
    "set_enabled",
    "enabled",
    "tracing",
    "span",
    "instant",
    "event_complete",
    "drain",
    "clear",
    "dropped",
    "export_chrome",
    "to_chrome_events",
    "RING_CAPACITY",
]

RING_CAPACITY = 65536  # events per thread before overwrite

_enabled = False

# Every registered per-thread ring, so drain() can see them all. Entries are
# _Ring objects; rings of dead threads stay until clear() — their events are
# part of the trace.
_rings_lock = threading.Lock()
_rings: list["_Ring"] = []
_local = threading.local()
# Bumped by clear(): a thread whose ring is of an older generation starts a
# new, registered one on its next append.
_generation = 0

# (name, tags, id) of the innermost live span — children inherit its tags
# and name its id as their parent.
_span_ctx: ContextVar[Optional[tuple[str, dict, int]]] = ContextVar(
    "repro_trace_span", default=None)

# Event ids, unique in the process; next() on a count is atomic under the GIL.
_ids = itertools.count(1)

# jax.profiler.TraceAnnotation, imported by the first enabled span.
_annotation = None


def _profiler_annotation():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


def _parent_id() -> Optional[int]:
    parent = _span_ctx.get()
    return None if parent is None else parent[2]


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


@contextmanager
def tracing(on: bool = True):
    """Temporarily flip tracing (benchmarks wrap their measured region)."""
    global _enabled
    prev = _enabled
    _enabled = bool(on)
    try:
        yield
    finally:
        _enabled = prev


class _Ring:
    """Single-writer event ring. Only its owning thread appends; drain()
    reads concurrently, which is safe for a stats ring (a torn read of the
    slot being overwritten is the worst case, and drain is a debugging/export
    operation, not a correctness path)."""

    __slots__ = ("tid", "tname", "gen", "buf", "head", "dropped")

    def __init__(self, tid: int, tname: str, gen: int):
        self.tid = tid
        self.tname = tname
        self.gen = gen
        self.buf: list = [None] * RING_CAPACITY
        self.head = 0      # next write index (monotonic, wraps via modulo)
        self.dropped = 0   # events overwritten after the ring first filled

    def append(self, ev: tuple) -> None:
        h = self.head
        if h >= RING_CAPACITY and self.buf[h % RING_CAPACITY] is not None:
            self.dropped += 1
            if self.dropped == 1:
                # cold path, once per ring lifetime: tell the event log the
                # exported trace will be incomplete for this thread. Imported
                # lazily — the hot append path must stay import-free.
                from .events import Severity, publish
                publish("trace.ring_drop", severity=Severity.WARNING,
                        message=f"trace ring for thread {self.tname!r} "
                                f"wrapped (capacity {RING_CAPACITY})",
                        thread=self.tname, capacity=RING_CAPACITY)
        self.buf[h % RING_CAPACITY] = ev
        self.head = h + 1

    def events(self) -> list:
        h = self.head
        if h <= RING_CAPACITY:
            return [e for e in self.buf[:h] if e is not None]
        i = h % RING_CAPACITY
        return [e for e in self.buf[i:] + self.buf[:i] if e is not None]


def _ring() -> _Ring:
    r = getattr(_local, "ring", None)
    if r is None or r.gen != _generation:
        t = threading.current_thread()
        with _rings_lock:
            r = _Ring(t.ident or 0, t.name, _generation)
            _rings.append(r)
        _local.ring = r
    return r


# Event tuples: ("X", name, ts, dur, tid_or_track, tags, id, parent) for
# complete events (tid_or_track is None → host thread row; a string → device
# virtual track), ("I", name, ts, tags, id, parent) for instants.


class _Span:
    """A live span: records (ts, dur) around its body, pushes itself as the
    contextvar parent so children inherit its tags, and holds a profiler
    annotation of the same name open over the same body."""

    __slots__ = ("name", "tags", "id", "parent", "_t0", "_token", "_ann")

    def __init__(self, name: str, tags: dict, parent: Optional[int]):
        self.name = name
        self.tags = tags
        self.id = next(_ids)
        self.parent = parent
        self._t0 = 0.0
        self._token = None
        self._ann = None

    def __enter__(self):
        self._token = _span_ctx.set((self.name, self.tags, self.id))
        args = dict(self.tags, id=self.id)
        if self.parent is not None:
            args["parent"] = self.parent
        self._ann = _profiler_annotation()(self.name, **args)
        self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        dur = time.monotonic() - self._t0
        self._ann.__exit__(*exc)
        self._ann = None
        if self._token is not None:
            _span_ctx.reset(self._token)
        _ring().append(("X", self.name, self._t0, dur, None, self.tags,
                        self.id, self.parent))
        return False


class _NoopSpan:
    """Shared singleton returned when tracing is off — the entire disabled
    cost of ``with span(...)`` is one bool test plus two empty methods."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


def span(name: str, **tags):
    """Context manager timing its body. Tags (tenant/device/zone/tier/op)
    merge over the enclosing span's tags."""
    if not _enabled:
        return _NOOP
    parent = _span_ctx.get()
    if parent is None:
        return _Span(name, tags, None)
    if parent[1]:
        merged = dict(parent[1])
        merged.update(tags)
        tags = merged
    return _Span(name, tags, parent[2])


def instant(name: str, **tags) -> None:
    """Zero-duration marker at now."""
    if not _enabled:
        return
    _ring().append(("I", name, time.monotonic(), tags, next(_ids),
                    _parent_id()))


def event_complete(name: str, ts: float, dur: float,
                   track: Optional[str] = None, **tags) -> None:
    """Record a complete event with EXPLICIT timestamps — how device virtual
    time enters the trace. The device model knows each transfer's claimed
    ``(start, service)`` window on the monotonic clock before it elapses;
    it calls this at submit time with ``track="dev0/zone3"`` and the event
    lands on that device row rather than the submitting thread's row.

    Its parent is the enclosing span, but it takes none of that span's tags
    and, being post-hoc, it never reaches the profiler: it lives in the ring
    and the Chrome export only."""
    if not _enabled:
        return
    _ring().append(("X", name, ts, dur, track, tags, next(_ids),
                    _parent_id()))


def dropped() -> int:
    with _rings_lock:
        return sum(r.dropped for r in _rings)


def drain() -> list[dict]:
    """Snapshot all recorded events as dicts (wall seconds), oldest-first
    per thread. Does not clear — export after a run, then :func:`clear`.
    ``id`` is the event's own id, ``parent`` its enclosing span's id (None
    at the top)."""
    with _rings_lock:
        rings = list(_rings)
    out = []
    for r in rings:
        for ev in r.events():
            if ev[0] == "X":
                _, name, ts, dur, track, tags, eid, parent = ev
                out.append({"type": "span", "name": name, "ts": ts,
                            "dur": dur, "track": track,
                            "tid": r.tid, "thread": r.tname, "tags": tags,
                            "id": eid, "parent": parent})
            else:
                _, name, ts, tags, eid, parent = ev
                out.append({"type": "instant", "name": name, "ts": ts,
                            "tid": r.tid, "thread": r.tname, "tags": tags,
                            "id": eid, "parent": parent})
    out.sort(key=lambda e: e["ts"])
    return out


def clear() -> None:
    """Drop all recorded events and rings (fresh trace). Every thread,
    long-lived pool workers included, starts a new ring on its next event."""
    global _generation
    with _rings_lock:
        _rings.clear()
        _generation += 1


_HOST_PID = 1
_DEVICE_PID = 2


def to_chrome_events(events: Optional[list[dict]] = None) -> list[dict]:
    """Convert drained events to Chrome ``trace_event`` dicts (ts/dur in µs,
    rebased so the trace starts near 0)."""
    if events is None:
        events = drain()
    if not events:
        return []
    t0 = min(e["ts"] for e in events)
    out: list[dict] = []
    # Metadata: name host threads; give each device track its own tid row.
    threads_seen: dict[int, str] = {}
    tracks: dict[str, int] = {}
    body: list[dict] = []
    for e in events:
        ts_us = (e["ts"] - t0) * 1e6
        args = dict(e["tags"]) if e["tags"] else {}
        args["id"] = e["id"]
        if e["parent"] is not None:
            args["parent"] = e["parent"]
        if e["type"] == "span" or e.get("track"):
            track = e.get("track")
            if track is not None:
                tid = tracks.setdefault(track, len(tracks) + 1)
                pid = _DEVICE_PID
            else:
                tid = e["tid"]
                pid = _HOST_PID
                threads_seen.setdefault(tid, e["thread"])
            body.append({"name": e["name"], "ph": "X", "pid": pid,
                         "tid": tid, "ts": ts_us,
                         "dur": e.get("dur", 0.0) * 1e6, "args": args})
        else:
            tid = e["tid"]
            threads_seen.setdefault(tid, e["thread"])
            body.append({"name": e["name"], "ph": "i", "pid": _HOST_PID,
                         "tid": tid, "ts": ts_us, "s": "t", "args": args})
    out.append({"name": "process_name", "ph": "M", "pid": _HOST_PID,
                "args": {"name": "host threads"}})
    out.append({"name": "process_name", "ph": "M", "pid": _DEVICE_PID,
                "args": {"name": "device virtual time"}})
    for tid, tname in threads_seen.items():
        out.append({"name": "thread_name", "ph": "M", "pid": _HOST_PID,
                    "tid": tid, "args": {"name": tname}})
    for track, tid in tracks.items():
        out.append({"name": "thread_name", "ph": "M", "pid": _DEVICE_PID,
                    "tid": tid, "args": {"name": track}})
    out.extend(body)
    return out


def export_chrome(path: str, events: Optional[list[dict]] = None) -> int:
    """Write ``{"traceEvents": [...]}`` JSON loadable in Perfetto /
    chrome://tracing. Returns the number of trace events written."""
    evs = to_chrome_events(events)
    with open(path, "w") as f:
        json.dump({"traceEvents": evs,
                   "displayTimeUnit": "ms",
                   "otherData": {"dropped_events": dropped()}}, f)
    return len(evs)
