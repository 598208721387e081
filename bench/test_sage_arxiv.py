"""The sage-arxiv configuration's pieces: its work counts by hand, the two
aggregation readers on synthetic trace events, the control at rehearsal
size, and its open cell end to end on the CPU, on the edge-list path (the
device's memory steered here so that the rehearsal's bucket takes it) and,
traced, with the per-layer metrics the CPU can read."""
from pathlib import Path

import numpy as np
import pytest

from benchlib import aggtrace, check, runner, spec, trace
from repro.runtime.tracing import Tracer

ROOT = Path(__file__).resolve().parents[1]
CELL = "sage-arxiv.open"
E = trace.Event


@pytest.fixture
def edge_path(monkeypatch):
    """1 MiB of device memory: the rehearsal's 384-row bucket cannot hold
    dense operands, so its graphs take the edge-list form."""
    from repro.runtime import gnn_server
    monkeypatch.setattr(gnn_server, "device_memory_bytes", lambda: 1 << 20)


def _cell():
    return spec.load_cell(ROOT, CELL)


# Hand counts at N = 10 nodes, E = 30 directed edges, widths 128 -> 256 ->
# 256 -> 40 (the layers aggregate at 128, 256 and 40):
#   agg ops (E + N) w: 40 * 128 = 5,120; 40 * 256 = 10,240; 40 * 40 = 1,600
#   agg bytes 4 (E + N + 1) + 8 N w: 164 + 10,240; 164 + 20,480; 164 + 3,200
def test_agg_work_by_hand():
    got = _cell().model.agg_work(_cell().config, 10, 30)
    assert got == [(5120.0, 10404.0), (10240.0, 20644.0), (1600.0, 3364.0)]


# work at N = 10, E = 30:
#   combines 4 N in out: 4*10*(128*256 + 256*256 + 256*40) = 4,341,760
#   biases N out: 10 * (256 + 256 + 40) = 5,520
#   BatchNorm and ReLU 5 N out after layers 1 and 2: 5 * 10 * 512 = 25,600
#   aggregation: 5,120 + 10,240 + 1,600 = 16,960            total 4,389,840
#   bytes 4 x (N F 1,280 + weights 2 (32,768 + 65,536 + 10,240) + biases 552
#              + BatchNorm 4 x 512 + E 30 + N + 1 11 + N C 400) = 4 x 221,409
def test_work_by_hand():
    assert _cell().model.work(_cell().config, 10, 30) == (4389840.0,
                                                          885636.0)


def _events(rows):
    H, D = "/host:CPU", "/device:TPU:0"
    return [
        E(H, "python", trace.WINDOW_SPAN, 1.0, 1.0),
        E(D, "XLA Ops", f"%fusion.4 = f32[{rows},256]{{1,0}} fusion(s32[9])",
          1.1, 0.2),
        E(D, "XLA Ops", f"%copy.5 = f32[1,{rows},40]{{1,0}} copy(%fusion.5)",
          1.9, 0.2),                                  # half in the window
        E(D, "XLA Ops", f"%fusion.30 = f32[{rows - 1},256]{{1,0}} "
          f"fusion(f32[1,{rows},256] %bitcast.9)", 1.3, 0.3),  # reads it
        E(D, "XLA Ops", f"%fusion.3 = f32[{rows},128]{{1,0}} fusion()",
          2.5, 0.1),                                  # after the window
    ]


def _ctx(profiled=(100.0, 104.0), traced=True):
    cell = _cell()
    marks = {"profile_start": {"t": profiled[0]},
             "profile_stop": {"t": profiled[1]}}
    return runner.Context(cell=cell, config=cell.config, seconds=51.0,
                          setup_s=0.0, window=(90.0, 141.0), served=[],
                          marks=marks, trace=object() if traced else None,
                          work=[], peaks=spec.peaks_for(ROOT, "TPU v5 lite"))


def _read(name, ctx):
    return spec.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read(ctx)


@pytest.fixture
def ring(monkeypatch):
    tr = Tracer()
    monkeypatch.setattr(aggtrace.spans, "ring", tr.spans)
    # two edges dispatches end in the profiled part, one before it, and a
    # dense one without attrs inside it
    tr.record("dispatch.device", 99.0, 99.5, 0,
              attrs={"nodes": 10, "edges": 30})
    tr.record("dispatch.device", 100.5, 101.0, 1,
              attrs={"nodes": 10, "edges": 30})
    tr.record("dispatch.device", 102.0, 102.5, 2,
              attrs={"nodes": 10, "edges": 30})
    tr.record("dispatch.device", 103.0, 103.1, 3)
    return tr


def test_aggregation_ops_are_the_bucket_plus_one_rows():
    rows = _cell().config["serving"]["bucket"] + 1
    assert aggtrace.op_seconds(_events(rows), rows) == pytest.approx(0.3)
    assert aggtrace.op_seconds(_events(rows + 5), rows) == 0.0
    no_window = [e for e in _events(rows) if e.name != trace.WINDOW_SPAN]
    assert aggtrace.op_seconds(no_window, rows) == 0.0


def test_readers_on_synthetic_events(ring, monkeypatch):
    rows = _cell().config["serving"]["bucket"] + 1
    monkeypatch.setattr(aggtrace, "xplane", lambda ctx: Path("x.xplane.pb"))
    monkeypatch.setattr(aggtrace, "load_ops", lambda path: _events(rows))
    ctx = _ctx()
    assert _read("agg_device_ms.open", ctx) == pytest.approx(150.0)
    # per dispatch: bytes bound in every layer, 10,404 + 20,644 + 3,364
    least = 2 * 34412 / 819e9
    assert _read("agg_roofline.open", ctx) == pytest.approx(
        100 * least / 0.3)


def test_readers_read_nothing_without_matching_ops(ring, monkeypatch):
    rows = _cell().config["serving"]["bucket"] + 1
    monkeypatch.setattr(aggtrace, "xplane", lambda ctx: Path("x.xplane.pb"))
    monkeypatch.setattr(aggtrace, "load_ops", lambda path: _events(rows + 5))
    for name in ("agg_device_ms.open", "agg_roofline.open"):
        assert _read(name, _ctx()) is None
        assert _read(name, _ctx(traced=False)) is None
    monkeypatch.setattr(aggtrace, "load_ops", lambda path: _events(rows))
    # no edges dispatch in the profiled part: the program had no such spans
    assert _read("agg_device_ms.open", _ctx(profiled=(200.0, 204.0))) is None


def test_control_fails_the_limit():
    """The reference at "high" (three bf16 passes) against itself at
    "highest", at the rehearsal's size on three seeds: every reading lies
    above the limit of `correct`."""
    cell = _cell()
    config = runner.config_for(cell, True)
    limit = config["correct"]["max_err_share"]
    for seed in (5, 6, 7):
        gs, params = runner.make_inputs(cell, config, seed)
        ref = check.reference_logits(cell.model, config, params, gs[:2])
        low = check.reference_logits(cell.model, config, params, gs[:2],
                                     precision="high")
        reading = max(check.err_share(a, b) for a, b in zip(low, ref))
        assert reading > limit, (seed, reading)


def _run(seed, traced=False, **kw):
    return runner.run_cell(ROOT, CELL, seed, 2.0, traced, t_process=0.0,
                           rehearse=True, log=lambda m: None, **kw)


def test_cell_serves_the_reference_from_edge_lists(edge_path):
    from repro.runtime.gnn_server import GraphServe
    seen = []
    orig = GraphServe._execute_batch

    def execute(self, batch):
        seen.extend(r.backend for r in batch)
        orig(self, batch)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(GraphServe, "_execute_batch", execute)
        result, side = _run(2**31 + 3)
    assert set(seen) == {"edges"}
    assert result["correct"], result["checks"]
    assert result["attempted"] == side["sent"] == side["answered"] > 0
    assert result["checks"]["max_err_share"]["value"] < 1e-5
    assert set(result["metrics"]) == {m["name"] for m in _cell().end_to_end}


def test_control_in_the_programs_place_is_not_correct(edge_path,
                                                      monkeypatch):
    """Every answer replaced, where it is produced, by the reference at
    "high" for its tenant: the harness's comparison reads `correct`
    false, on the limit alone."""
    from repro.runtime.gnn_server import GraphServe
    made = {}
    make_inputs = runner.make_inputs

    def capture(cell, config, seed):
        gs, params = make_inputs(cell, config, seed)
        made["graphs"] = gs
        made["high"] = check.reference_logits(cell.model, config, params, gs,
                                              precision="high")
        return gs, params

    def tenant(r):
        n = made["graphs"][0]["num_nodes"]
        x = np.asarray(r.pg.features)[:n]
        return int(np.argmin([np.abs(x - g["features"]).max()
                              for g in made["graphs"]]))

    orig = GraphServe._execute_batch

    def execute(self, batch):
        orig(self, batch)
        for r in batch:
            r.logits = made["high"][tenant(r)]

    monkeypatch.setattr(runner, "make_inputs", capture)
    monkeypatch.setattr(GraphServe, "_execute_batch", execute)
    result, side = _run(13)
    assert side["compared"] > 0
    c = result["checks"]
    assert all(v["value"] <= v["limit"] for k, v in c.items()
               if k != "max_err_share")
    assert c["max_err_share"]["value"] > c["max_err_share"]["limit"]
    assert not result["correct"]


def test_traced_rehearsal_reports_what_the_cpu_can_read(edge_path):
    result, _ = _run(21, traced=True)
    assert result["correct"], result["checks"]
    got = set(result["metrics"])
    assert {"dispatch_ms.open", "queue_wait_ms.open",
            "host_stage_ms.open"} <= got
    # no TPU plane on the CPU: the aggregation's device time reads nothing
    assert not got & {"agg_device_ms.open", "agg_roofline.open"}
