"""The `program_span` readers (`benchlib/spans.py`): each new metric on a
hand-built ring and window, spans that straddle the window's edges and its
profiled part, a program without the recorder, and a traced CPU rehearsal
of one closed and one open cell."""
import sys
from pathlib import Path

import pytest

from benchlib import runner, spans, spec
from repro.runtime.tracing import Tracer

ROOT = Path(__file__).resolve().parents[1]
NEW = ("queue_wait_ms.open", "stack_ms.closed", "h2d_ms.closed",
       "device_wait_ms.closed", "h2d_mb_per_request.closed")
STAGES = ("dispatch.stack", "dispatch.h2d", "dispatch.operands",
          "dispatch.device", "dispatch.d2h")
SLOT_BYTES = 1000


def _context(window=(100.0, 110.0), profiled=(104.0, 106.0)):
    marks = {}
    if profiled is not None:
        marks = {"profile_start": {"t": profiled[0]},
                 "profile_stop": {"t": profiled[1]}}
    return runner.Context(cell=None, config={}, seconds=window[1] - window[0],
                          setup_s=0.0, window=window, served=[], marks=marks,
                          trace=None, work=[], peaks=None)


def _dispatch(tr, serial, t, lengths, filled, slots=4):
    """One dispatch from `t`: its five stages back to back, `lengths` long
    each, the features of `slots` slots sent."""
    start = t
    for name, n in zip(STAGES, lengths):
        attrs = ({"bytes": slots * SLOT_BYTES, "filled": filled}
                 if name == "dispatch.h2d" else None)
        tr.record(name, t, t + n, serial, attrs=attrs)
        t += n
    tr.record("dispatch", start, t + 1e-4, serial)


@pytest.fixture
def ring(monkeypatch):
    tr = Tracer()
    monkeypatch.setattr(spans, "ring", tr.spans)
    #               stack  h2d   ops   device d2h
    _dispatch(tr, 0, 99.0, (0.40, 0.05, 0.01, 0.02, 0.01), 4)  # before
    _dispatch(tr, 1, 99.8, (0.30, 0.06, 0.01, 0.03, 0.01), 2)  # straddles t0
    _dispatch(tr, 2, 101.0, (0.38, 0.07, 0.01, 0.04, 0.01), 4)
    _dispatch(tr, 3, 105.0, (0.19, 0.19, 0.19, 0.19, 0.19), 4)  # profiled
    _dispatch(tr, 4, 109.5, (0.36, 0.20, 0.01, 0.05, 0.01), 4)  # straddles t1
    _dispatch(tr, 5, 111.0, (5.00, 5.00, 5.00, 5.00, 5.00), 4)  # after
    # a second engine's serial 2, outside every window
    _dispatch(tr, 2, 50.0, (0.1, 0.1, 0.1, 0.1, 0.1), 1)
    # request.queue: (start, end); ends at 99.9, 103.5, 105.0, 108.0,
    # 109.0 and 110.0 — three counted
    for uid, (s, e) in enumerate([(99.0, 99.9), (100.5, 103.5),
                                  (104.5, 105.0), (107.9, 108.0),
                                  (108.0, 109.0), (109.0, 110.0)]):
        tr.record("request.queue", s, e, uid, parent=0)
    return tr


def _read(name, ctx):
    return spec.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read(ctx)


def test_stage_means_count_what_ends_inside_the_window(ring):
    ctx = _context()
    # dispatch 1's stack ends at 100.1, inside; dispatch 4's h2d ends at
    # 110.06, outside: the stack reads dispatches 1, 2, 4, the rest 1, 2
    assert _read("stack_ms.closed", ctx) == pytest.approx(
        1e3 * (0.30 + 0.38 + 0.36) / 3)
    assert _read("h2d_ms.closed", ctx) == pytest.approx(1e3 * (0.06 + 0.07) / 2)
    assert _read("device_wait_ms.closed", ctx) == pytest.approx(
        1e3 * (0.03 + 0.04) / 2)


def test_h2d_bytes_per_request_follow_each_dispatch(ring):
    # dispatch 1 answered 2 requests, dispatch 2 answered 4, each sent 4
    # slots
    assert _read("h2d_mb_per_request.closed", _context()) == pytest.approx(
        8 * SLOT_BYTES / 6 / 1e6)


def test_queue_wait_is_the_median_of_counted_spans(ring):
    # counted: 3.0 s, 0.1 s, 1.0 s (99.9 is before the window, 105.0 in
    # its profiled part, 110.0 at its open end)
    assert _read("queue_wait_ms.open", _context()) == pytest.approx(1e3)
    # without a profiled part the span ending at 105.0 counts as well
    assert _read("queue_wait_ms.open", _context(profiled=None)) == \
        pytest.approx(1e3 * (0.5 + 1.0) / 2)


def test_nothing_counted_reads_nothing(ring):
    ctx = _context(window=(200.0, 210.0))
    assert all(_read(name, ctx) is None for name in NEW)


def test_program_without_the_recorder_reads_nothing(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    assert spans.ring() is None
    assert all(_read(name, _context()) is None for name in NEW)


@pytest.mark.parametrize("workload", ["gcn-cora.closed", "gcn-cora.open"])
def test_traced_rehearsal_reports_every_new_metric(workload):
    cell = spec.load_cell(ROOT, workload)
    mine = {m["name"] for m in cell.per_layer} & set(NEW)
    assert mine
    result, side = runner.run_cell(ROOT, workload, 21, 2.0, True,
                                   t_process=0.0, rehearse=True,
                                   log=lambda m: None)
    assert result["correct"], result["checks"]
    got = result["metrics"]
    assert mine <= set(got)
    assert all(got[name]["value"] > 0 for name in mine)
    if "h2d_mb_per_request.closed" in mine:
        g = cell.config["rehearsal"]
        slot = g["serving"]["bucket"] * g["in_feats"] * 4 / 1e6
        # every slot's features are sent; a partial batch reads higher
        assert got["h2d_mb_per_request.closed"]["value"] >= slot
