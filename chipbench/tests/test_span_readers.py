"""The readers of the program's own spans on a synthetic context: the
value, and None where the span is absent (a program without it)."""
import pytest

import run


def _span(name, dur):
    return {"type": "span", "name": name, "ts": 0.0, "dur": dur,
            "track": None, "tid": 1, "thread": "t", "tags": {},
            "id": 1, "parent": None}


def _ctx(spans, commands=0):
    reg = {"offload.commands": commands} if commands else {}
    return run.Context([], 0.0, 0.0, 4096, spans=spans, reg=reg)


SPANS = [_span("tier.put", 0.1), _span("tier.put", 0.3),
         _span("tier.run", 0.002), _span("tier.run", 0.004),
         _span("stage.put", 1.5), _span("stage.put", 0.5),
         _span("stage.copy", 4.0), _span("stage.copy", 6.0),
         _span("stage.serve_chunk", 9.0)]


@pytest.mark.parametrize("metric, want", [
    ("put_ms.csd", 200.0),          # mean tier.put, ms
    ("put_us.ycsb", 200000.0),      # mean tier.put, us
    ("run_us.ycsb", 3000.0),        # mean tier.run, us
    ("put_ms.scan", 1000.0),        # stage.put summed over two offloads
    ("staging_ms.scan", 5000.0),    # stage.copy summed over two offloads
])
def test_reader_value(metric, want):
    got = run.load_metric(metric)(_ctx(SPANS, commands=2))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("metric", ["put_ms.csd", "put_us.ycsb",
                                    "run_us.ycsb", "put_ms.scan",
                                    "staging_ms.scan"])
def test_reader_finds_nothing_without_its_span(metric):
    others = [s for s in SPANS if s["name"] == "stage.serve_chunk"]
    assert run.load_metric(metric)(_ctx(others, commands=2)) is None
    assert run.load_metric(metric)(_ctx([], commands=0)) is None


@pytest.mark.parametrize("metric", ["put_ms.scan", "staging_ms.scan"])
def test_per_offload_readers_need_completed_offloads(metric):
    assert run.load_metric(metric)(_ctx(SPANS, commands=0)) is None
