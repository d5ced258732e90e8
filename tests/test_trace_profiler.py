"""Program spans on the profiler's clock: span ids and parents, the
``jax.profiler`` bridge, gather-pool jobs that keep their offload, the
timed host-to-HBM put and staging copy, and the executables' names.

A CPU profiler trace carries ``TraceAnnotation``s on ``/host:CPU``, so the
bridge is checked here without a chip."""
from __future__ import annotations

import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.array import OffloadScheduler, StripedZoneArray
from repro.core import NvmCsd, vm
from repro.core.programs import filter_count
from repro.kernels.zone_filter import ops as zf_ops
from repro.telemetry import trace
from repro.telemetry.metrics import registry
from repro.zns import ZonedDevice

BLOCK = 4096
STRIPE = 64                  # 256 KiB chunks: each staging copy is real work
N_FULL = 4                   # full chunks: two batch groups of two members
TAIL = 2                     # a short tail chunk runs through execute_extent
ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _fresh_trace():
    trace.set_enabled(False)
    trace.clear()
    yield
    trace.set_enabled(False)
    trace.clear()


@pytest.fixture(scope="module")
def sched():
    """Two raid0 members; the zone holds four full chunks (two staged
    groups, each copied from both members) and a tail chunk."""
    devs = [ZonedDevice(num_zones=1, zone_bytes=4 << 20, block_bytes=BLOCK)
            for _ in range(2)]
    array = StripedZoneArray(devs, stripe_blocks=STRIPE)
    rng = np.random.default_rng(7)
    n = (N_FULL * STRIPE + TAIL) * BLOCK // 4
    array.zone_append(0, rng.integers(-1000, 1000, n, dtype=np.int32))
    s = OffloadScheduler(array)
    s.nvm_cmd_bpf_run(PROGRAM, 0)          # compile outside every test
    yield s
    s.close()
    array.close()


PROGRAM = filter_count("int32", "gt", 0)


def _by_name(events, name):
    return [e for e in events if e["name"] == name]


def _settled_drain(want: int = 0, timeout: float = 5.0) -> list:
    """The ring once every recorded parent is in it too, and ``want``
    staging copies: a pool job's span closes just after the job hands the
    offload its part."""
    deadline = time.monotonic() + timeout
    while True:
        events = trace.drain()
        ids = {e["id"] for e in events}
        if ((all(e["parent"] in ids for e in events if e["parent"])
             and len(_by_name(events, "stage.copy")) >= want)
                or time.monotonic() > deadline):
            return events
        time.sleep(0.01)


def _traced_offload(sched):
    with trace.tracing(True):
        sched.nvm_cmd_bpf_run(PROGRAM, 0)
    events = _settled_drain()
    (ex,) = _by_name(events, "offload.execute")
    return events, ex


# ------------------------------------------------------------ ids, parents
def test_spans_carry_ids_and_their_parents():
    with trace.tracing(True):
        with trace.span("outer", tenant="t0"):
            with trace.span("inner"):
                trace.instant("mark")
            trace.event_complete("dev.read", 1.0, 0.5, track="dev0/z0")
    evs = {e["name"]: e for e in trace.drain()}
    outer = evs["outer"]
    assert outer["parent"] is None
    assert evs["inner"]["parent"] == outer["id"]
    assert evs["mark"]["parent"] == evs["inner"]["id"]
    assert evs["dev.read"]["parent"] == outer["id"]
    # a post-hoc event takes its parent, not the parent's tags
    assert evs["dev.read"]["tags"] == {}
    assert len({e["id"] for e in evs.values()}) == 4
    chrome = {e["name"]: e for e in trace.to_chrome_events()
              if e["ph"] != "M"}
    assert chrome["inner"]["args"] == {"tenant": "t0", "id": evs["inner"]["id"],
                                       "parent": outer["id"]}
    assert "parent" not in chrome["outer"]["args"]


def test_disabled_span_builds_no_annotation(monkeypatch):
    def refuse():
        raise AssertionError("a disabled span built a profiler annotation")
    monkeypatch.setattr(trace, "_profiler_annotation", refuse)
    assert trace.span("offload.execute", offload=1) is trace._NOOP
    with trace.span("offload.execute", offload=1):
        pass
    assert trace.drain() == []


def test_disabled_span_does_not_import_jax():
    code = ("import sys\n"
            "from repro.telemetry import trace\n"
            "with trace.span('offload.execute', offload=1):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env={"PYTHONPATH": str(ROOT / "src"),
                            "PATH": "/usr/bin:/bin"},
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


# -------------------------------------------------------- profiler bridge
def _host_events(pb: str) -> list:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(pb).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                args = {k: v for k, v in e.stats}
                if "id" in args:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, args))
    return out


def test_program_spans_reach_the_profiler_nested_as_in_the_ring(
        sched, tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace.tracing(True):
            sched.nvm_cmd_bpf_run(PROGRAM, 0)
        sched.nvm_cmd_bpf_run(PROGRAM, 0)     # untraced: leaves no span
    finally:
        jax.profiler.stop_trace()
    ring = {e["id"]: e for e in _settled_drain() if e["type"] == "span"}
    (pb,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    prof = {args["id"]: (name, a, b, args)
            for name, a, b, args in _host_events(str(pb))}
    names = [v[0] for v in prof.values()]
    # one offload traced, so one of each: the untraced one left nothing
    for name in ("offload.execute", "offload.stage.compute", "tier.put"):
        assert names.count(name) == 1, name
    # every annotation is a span of the ring, under the same parent
    for eid, (name, _a, _b, args) in prof.items():
        assert ring[eid]["name"] == name
        assert args.get("parent") == ring[eid]["parent"]
    # tier.put -> stage.serve_chunk -> offload.stage.compute ->
    # offload.execute, each inside its parent on the profiler's clock
    (put,) = [v for v in prof.values() if v[0] == "tier.put"]
    chain, node = [], put
    while "parent" in node[3]:
        parent = prof[node[3]["parent"]]
        assert parent[1] <= node[1] and node[2] <= parent[2]
        chain.append(parent[0])
        node = parent
    assert chain == ["stage.serve_chunk", "offload.stage.compute",
                     "offload.execute"]
    (ex,) = [v for v in prof.values() if v[0] == "offload.execute"]
    assert ex[3]["offload"] == put[3]["offload"]


# ----------------------------------------------------- the gather pool
def test_gather_pool_spans_keep_their_offload_and_parent(sched):
    events, ex = _traced_offload(sched)
    oid = ex["tags"]["offload"]
    mine = {e["id"] for e in events if e["tags"].get("offload") == oid}
    pool = _by_name(events, "gather.exec")
    assert pool
    for e in pool:
        assert e["tags"]["offload"] == oid
        assert e["parent"] in mine
    pool_ids = {e["id"] for e in pool}
    for name in ("stage.copy", "stage.materialize", "stage.put"):
        got = _by_name(events, name)
        assert got, name
        for e in got:
            assert e["tags"]["offload"] == oid, name
            assert e["parent"] in pool_ids, name
            assert e["tid"] != ex["tid"], name    # off the dispatcher


def test_stage_put_is_timed_once_a_group(sched):
    events, ex = _traced_offload(sched)
    puts = _by_name(events, "stage.put")
    dispatches = _by_name(events, "stage.dispatch")
    assert len(puts) == len(dispatches) == N_FULL // 2
    for put in puts:
        assert put["dur"] >= 0.0
        assert put["ts"] >= ex["ts"]
    # the per-chunk tail puts its own pages, inside its serve_chunk
    (tput,) = _by_name(events, "tier.put")
    assert tput["tags"]["nbytes"] == TAIL * BLOCK
    assert _by_name(events, "tier.run")


def test_stage_copy_sums_to_the_staging_histogram(sched):
    before = registry().snapshot()
    with trace.tracing(True):
        sched.nvm_cmd_bpf_run(PROGRAM, 0)
    staged = registry().delta(before)["sched.stage.staging_seconds.sum"]
    copies = _by_name(_settled_drain(want=N_FULL), "stage.copy")
    assert len(copies) == N_FULL          # two groups, two member runs each
    total = sum(e["dur"] for e in copies)
    # the span encloses the timed copy and its bookkeeping, nothing more
    assert staged - 1e-9 <= total <= staged + 1e-3 * len(copies)


def test_no_stage_staging_span_and_no_removed_offload_series(sched):
    before = registry().snapshot()
    events, _ = _traced_offload(sched)
    assert not _by_name(events, "stage.staging")
    delta = registry().delta(before)
    assert delta["offload.commands"] == 1
    assert not [k for k in registry().snapshot()
                if k.startswith(("offload.dispatches", "offload.exec_",
                                 "offload.read_", "offload.overlap_"))]


# ------------------------------------------------- the JIT scan's steps
CSD_PAGES = 40               # with a 16-page tile: two full steps and a tail


@pytest.fixture
def csd_tiled(monkeypatch):
    """``NvmCsd`` over one zone of ``CSD_PAGES`` 4 KiB blocks, its JIT scan
    stepping 16 pages at a time."""
    monkeypatch.setattr(vm, "_SCAN_STEP_BYTES", 16 * BLOCK)
    dev = ZonedDevice(num_zones=1, zone_bytes=1 << 20, block_bytes=BLOCK)
    dev.zone_append(0, np.arange(CSD_PAGES * BLOCK // 4, dtype=np.int32))
    csd = NvmCsd(dev)
    csd.run_and_fetch(PROGRAM, 0)          # compiled outside the trace
    return csd


def test_traced_tier_run_carries_the_scan_steps(csd_tiled):
    with trace.tracing(True):
        got, _ = csd_tiled.run_and_fetch(PROGRAM, 0)
    (run,) = _by_name(trace.drain(), "tier.run")
    k = vm.pages_per_step(CSD_PAGES, BLOCK)
    assert k == 16
    assert run["tags"]["steps"] == -(-CSD_PAGES // k) == 3
    assert int(got) == CSD_PAGES * BLOCK // 4 - 1     # every element but 0


def test_untraced_offload_records_no_steps(csd_tiled):
    csd_tiled.run_and_fetch(PROGRAM, 0)
    assert trace.drain() == []


# ------------------------------------------------------ executable names
@pytest.mark.parametrize("build, name", [
    (lambda p: vm.jit_program(p, 4, 1024), "zcsd_jit_scan"),
    (lambda p: vm.jit_program_batched(p, 2, 4, 1024),
     "zcsd_jit_scan_batched"),
    (lambda p: zf_ops.kernel_program(p, 4, 1024), "zcsd_kernel_scan"),
    (lambda p: zf_ops.kernel_program_batched(p, 2, 4, 1024),
     "zcsd_kernel_scan_batched"),
])
def test_executables_are_named_by_tier_and_shape(build, name):
    jp = build(PROGRAM)
    assert f"HloModule jit_{name}," in jp.fn.as_text()
    pages = np.arange(jp.n_pages * jp.page_elems, dtype=np.int32) - 2000
    pages = pages.reshape(jp.n_pages, jp.page_elems)
    if name.endswith("_batched"):
        pages = np.stack([pages, pages])
    want = int((pages > 0).sum(axis=(-2, -1)).sum())
    assert int(np.asarray(jp(pages)).sum()) == want
    assert int(np.asarray(jp(jp.put(pages))).sum()) == want
