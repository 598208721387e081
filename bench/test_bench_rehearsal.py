"""Every cell end to end on the CPU at its tiny size: the window's own
answers against the plain reference, and `correct` turning false when the
timed path is broken underneath."""
from pathlib import Path

import numpy as np
import pytest

from benchlib import runner, spec

ROOT = Path(__file__).resolve().parents[1]
CELLS = ("gat-cora.open", "gcn-cora.open", "gcn-cora.closed",
         "gat-cora.closed")


def _run(workload, seed=11, **kw):
    return runner.run_cell(ROOT, workload, seed, 1.0, False, t_process=0.0,
                           rehearse=True, log=lambda m: None, **kw)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_serves_the_reference(workload):
    result, side = _run(workload)
    assert result["correct"], result["checks"]
    assert result["attempted"] == side["sent"] == side["answered"] > 0
    assert side["compared"] == side["answered"]
    assert result["failed"] == 0
    # on the CPU the program's fp32 path is fp32 throughout
    assert result["checks"]["max_err_share"]["value"] < 1e-5
    want = {m["name"] for m in spec.load_cell(ROOT, workload).end_to_end}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"


def _negate_first_answer(orig):
    def execute(self, batch):
        orig(self, batch)
        batch[0].logits = -batch[0].logits
    return execute


def _cross_slots(orig):
    def execute(self, batch):
        orig(self, batch)
        if len(batch) > 1:
            first = batch[0].logits
            for a, b in zip(batch, batch[1:]):
                a.logits = b.logits
            batch[-1].logits = first
    return execute


def _shift_rows(orig):
    def execute(self, batch):
        orig(self, batch)
        for r in batch:
            r.logits = np.roll(r.logits, 1, axis=0)
    return execute


def _drop_one_answer(orig):
    def execute(self, batch):
        orig(self, batch)
        batch[-1].logits = None
    return execute


@pytest.mark.parametrize("fault", [_negate_first_answer, _cross_slots,
                                   _shift_rows, _drop_one_answer])
def test_broken_answers_are_not_correct(fault, monkeypatch):
    """An answer altered where it is produced: one request's logits, slots
    swapped within a batch, rows off by one when unpadding, an answer
    lost, each planted in the engine's device stage."""
    from repro.runtime.gnn_server import GraphServe
    monkeypatch.setattr(GraphServe, "_execute_batch",
                        fault(GraphServe._execute_batch))
    result, _ = _run("gcn-cora.closed", seed=12)
    assert not result["correct"]


@pytest.mark.parametrize("config", ["gcn-cora", "gat-cora"])
def test_control_in_the_programs_place_is_not_correct(config, monkeypatch):
    """The control end to end: every answer the engine serves is replaced,
    where it is produced, by the reference at "high" for its tenant, and the
    harness's own comparison reads `correct` false."""
    from benchlib import check
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
    result, side = _run(f"{config}.closed", seed=13)
    assert side["compared"] > 0
    assert not result["correct"]
    c = result["checks"]
    assert all(v["value"] <= v["limit"] for k, v in c.items()
               if k != "max_err_share")
    assert c["max_err_share"]["value"] > c["max_err_share"]["limit"]


@pytest.mark.parametrize("config", ["gcn-cora", "gat-cora"])
def test_control_fails_the_limit(config):
    """The control, the reference with its products at "high" (three bf16
    passes) in the program's place, at the cell's own graph size and widths
    on three seeds: every reading lies above the limit of `correct`."""
    from benchlib import check
    cell = spec.load_cell(ROOT, f"{config}.closed")
    limit = cell.config["correct"]["max_err_share"]
    for seed in (5, 6, 7):
        gs, params = runner.make_inputs(cell, cell.config, seed)
        ref = check.reference_logits(cell.model, cell.config, params, gs[:2])
        low = check.reference_logits(cell.model, cell.config, params, gs[:2],
                                     precision="high")
        reading = max(check.err_share(a, b) for a, b in zip(low, ref))
        assert reading > limit, (seed, reading)
