"""The yardstick's pieces: peaks, work counts, traffic, trace reduction,
and finding a cell's files by name."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchlib import graphs, spec, trace, traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CORA = dict(num_nodes=2708, num_edges=5429, num_feats=1433, num_classes=7)


def test_peaks_keyed_by_device_kind():
    p = spec.peaks_for(ROOT, "TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        spec.peaks_for(ROOT, "TPU v9 imaginary")


def test_cora_shape_at_seed_0():
    g = graphs.planetoid_like(**CORA, seed=0)
    assert g["features"].shape == (2708, 1433)
    assert g["edge_index"].shape == (2, 10832)
    ei = g["edge_index"]
    assert (ei[0] != ei[1]).all()
    pairs = set(zip(ei[0].tolist(), ei[1].tolist()))
    assert all((d, s) in pairs for s, d in pairs)          # undirected
    np.testing.assert_allclose(g["features"].sum(1), 1.0, rtol=1e-6)


# Hand counts for one Cora-shaped request: N = 2708 nodes, E = 10832
# directed edges (seed 0), E + N = 13540 with self loops, F = 1433.
@pytest.mark.parametrize("config,flops,nbytes", [
    # GCN 1433 -> 64 -> 7:
    #   2NFH 496,712,192 + 2(E+N)H 1,733,120 + bias/relu 2NH 346,624
    #   + 2NHC 2,426,368 + 2(E+N)C 189,560 + bias NC 18,956
    #   bytes 4 x (NF 3,880,564 + FH 91,712 + H 64 + HC 448 + C 7
    #              + E 10,832 + N+1 2,709 + NC 18,956)
    ("gcn-cora", 501_426_820, 16_021_168),
    # GAT 8 x 8 heads, then 1 head of 7:
    #   layer 1: 2NFD 496,712,192 + 4ND 693,248 + 7(E+N)h 758,240
    #            + 2(E+N)D 1,733,120 + bias ND 173,312 + ELU ND 173,312
    #   layer 2: 2N64C 2,426,368 + 4NC 75,824 + 7(E+N) 94,780
    #            + 2(E+N)C 189,560 + bias NC 18,956
    #   bytes 4 x (NF + E + N+1 + NC = 3,913,061; FD + 2hd + D = 91,904;
    #              64C + 2C + C = 469)
    ("gat-cora", 503_048_912, 16_021_736),
])
def test_work_matches_hand_count(config, flops, nbytes):
    cell = spec.load_cell(ROOT, f"{config}.closed")
    got = cell.model.work(cell.config, 2708, 10832)
    assert got == (float(flops), float(nbytes))


def test_open_schedule_gives_every_seed_the_same_work():
    mix = {"loop": "open", "rate_rps": 30.0, "arrivals": "stratified"}
    a = traffic.open_schedule(mix, 20.0, 8, seed=1)
    b = traffic.open_schedule(mix, 20.0, 8, seed=2**31 + 17)
    assert len(a) == len(b) == 600
    gaps = [np.diff([d for d, _ in s] + [20.0]) for s in (a, b)]
    np.testing.assert_allclose(np.sort(gaps[0]), np.sort(gaps[1]))
    assert abs(gaps[0].sum() - 20.0) < 1e-9
    assert not np.allclose(gaps[0], gaps[1])               # another order
    for s in (a, b):
        assert np.bincount([k for _, k in s]).tolist() == [75] * 8
        assert all(0 <= d < 20.0 for d, _ in s)


def test_closed_order_visits_every_tenant_each_round():
    order = traffic.closed_order({"tenant_choice": "round_robin"}, 8, seed=3,
                                 rounds=4)
    for r in range(4):
        assert sorted(order[8 * r: 8 * r + 8]) == list(range(8))


def test_poisson_arrivals_are_independent_gaps():
    mix = {"loop": "open", "rate_rps": 50.0, "arrivals": "poisson"}
    counts = []
    for seed in range(20):
        due = [d for d, _ in traffic.open_schedule(mix, 20.0, 8, seed)]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 20.0
        counts.append(len(due))
    # 1000 expected, standard deviation about 32
    assert 900 < np.mean(counts) < 1100 and len(set(counts)) > 10


def test_bursts_keep_the_mean_rate():
    mix = {"loop": "open", "rate_rps": 20.0, "arrivals": "stratified",
           "burst": {"every_s": 4.0, "for_s": 1.0, "factor": 6.0}}
    due = np.array([d for d, _ in traffic.open_schedule(mix, 40.0, 8, 7)])
    assert len(due) == 800 and np.all(np.diff(due) >= 0) and due[-1] < 40.0
    # 6 / (6 + 3) of the arrivals fall in the first second of every four
    assert np.mean(due % 4.0 < 1.0) == pytest.approx(6 / 9, abs=0.01)
    with pytest.raises(ValueError):
        traffic.open_schedule({**mix, "burst": {"every_s": 1.0, "for_s": 2.0,
                                                "factor": 3.0}}, 40.0, 8, 7)


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_zipf_tenants_take_exact_shares(loop):
    mix = {"loop": loop, "rate_rps": 16.0, "tenant_choice": "zipf",
           "zipf_exponent": 1.0}
    want = sorted([480, 240, 160, 120, 96, 80], reverse=True)   # of 1176
    shares = []
    for seed in (1, 2):
        if loop == "open":
            ks = [k for _, k in traffic.open_schedule(mix, 73.5, 6, seed)]
        else:
            ks = traffic.closed_order(mix, 6, seed, rounds=196)
        n = np.bincount(ks, minlength=6)
        assert sorted(n.tolist(), reverse=True) == want
        shares.append(n)
    assert not np.array_equal(*shares)     # the seed picks the hot tenant


@pytest.mark.parametrize("params", [{"rate": 3.0}, {"loop": "open",
                                                   "zipf": 1.1}])
def test_unknown_traffic_parameters_are_refused(params):
    with pytest.raises(ValueError, match="unknown traffic parameters"):
        traffic.check(params)


@pytest.mark.parametrize("params", [
    {"loop": "open", "rate_rps": 3.0, "arrivals": "gamma"},
    {"loop": "open", "rate_rps": 3.0, "tenant_choice": "hash"}])
def test_unknown_traffic_choices_are_refused(params):
    with pytest.raises(ValueError, match="unknown"):
        traffic.open_schedule(params, 5.0, 4, 1)


def _synthetic():
    H, D = "/host:CPU", "/device:TPU:0"
    E = trace.Event
    return [
        E(H, "python", trace.WINDOW_SPAN, 1.0, 1.0),
        E(H, "dispatch", "np.stack", 1.2, 0.3),
        E(H, "dispatch", "h2d", 1.6, 0.4),
        E(H, "dispatch", "$threading.py:323 wait", 1.0, 1.0),     # a wait
        E(D, "XLA Modules", "jit__forward(7)", 0.9, 0.3),
        E(D, "XLA Ops", "%fusion.1", 0.9, 0.2),                 # clipped
        E(D, "XLA Ops", "%dot.2", 1.05, 0.15),                  # overlaps
        E(D, "XLA Modules", "jit_stack(3)", 1.5, 0.1),
        E(D, "XLA Ops", "%concatenate", 1.5, 0.1),
        E(D, "XLA Ops", "%late", 2.5, 0.1),                     # outside
    ]


def test_trace_reduction_by_hand():
    s = trace.summarize(_synthetic())
    assert s.chips == 1
    assert s.window_s == pytest.approx(1.0)
    assert s.busy_s == pytest.approx(0.3)          # [1.0,1.2] + [1.5,1.6]
    assert s.module_seconds(r"^jit__forward\(") == pytest.approx(0.2)
    assert s.module_seconds(r"^jit_stack") == pytest.approx(0.1)
    assert [n for n, _ in s.device_ops] == [
        "jit__forward/%dot.2", "jit__forward/%fusion.1",
        "jit_stack/%concatenate"]
    assert s.idle_gaps == [("idle: h2d", pytest.approx(0.4)),
                           ("idle: np.stack", pytest.approx(0.3))]


def test_trace_without_window_or_device_reads_nothing():
    ev = _synthetic()
    assert trace.summarize([e for e in ev if e.name != trace.WINDOW_SPAN]) is None
    assert trace.summarize([e for e in ev if e.plane.startswith("/host")]) is None


def test_recorded_chip_trace():
    """A few seconds of gcn-cora.closed on one TPU v5 lite, reduced to the
    events the benchmark reads."""
    path = BENCH / "testdata" / "gcn-cora.closed.v5e.events.json.gz"
    ev = trace.load_events(path)
    s = trace.summarize(ev)
    assert s is not None and s.chips == 1
    assert s.window_s == pytest.approx(3.912537318, rel=1e-9)
    assert s.busy_s == pytest.approx(0.012787281, rel=1e-6)
    assert s.module_seconds(r"^jit__forward\(") == pytest.approx(0.00451845,
                                                                 rel=1e-6)
    assert s.idle_gaps[0] == ("idle: $graph.py:335 stack_padded",
                              pytest.approx(0.384404676, rel=1e-6))
    ops = sum(e.dur for e in ev if e.line == "XLA Ops")
    assert s.busy_s <= ops + 1e-12
    assert s.module_seconds(r"^jit__forward\b") > 0
    assert s.busy_s + sum(g for _, g in s.idle_gaps) <= s.window_s + 1e-9
    assert [v for _, v in s.device_ops] == sorted(
        (v for _, v in s.device_ops), reverse=True)


def _copy_bench(dst: Path) -> None:
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")


def test_run_refuses_without_program(tmp_path):
    _copy_bench(tmp_path)
    out = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         "gcn-cora.closed", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no program to measure" in out.stderr


def test_run_refuses_off_the_chip(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "gcn-cora.closed", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr


def test_new_cell_and_metric_from_new_files_alone(tmp_path):
    """A later PR adds a configuration, a mix, a cell and a metric by adding
    files and entries: the harness finds them by name."""
    from benchlib import runner
    _copy_bench(tmp_path)
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "gcn-cora.json").read_text())
    cfg["name"] = "gcn-cora-wide"
    cfg["rehearsal"]["serving"]["tenants"] = 3
    (b / "configs" / "gcn-cora-wide.json").write_text(json.dumps(cfg))
    # a mix no cell has yet: Poisson arrivals in bursts, Zipf tenants
    (b / "traffic" / "trickle.json").write_text(json.dumps(
        {"loop": "open", "rate_rps": 6.0, "arrivals": "poisson",
         "burst": {"every_s": 0.5, "for_s": 0.1, "factor": 4.0},
         "tenant_choice": "zipf", "zipf_exponent": 1.1}))
    (b / "metrics" / "answers_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx.served))\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "gcn-cora-wide", "source": "x",
                             "file": "bench/configs/gcn-cora-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "gcn-cora-wide.trickle",
                               "config": "gcn-cora-wide",
                               "traffic": "trickle", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "answers_seen", "unit": "req",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "throughput_rps",
                               "workloads": ["gcn-cora-wide.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    result, side = runner.run_cell(tmp_path, "gcn-cora-wide.trickle", 5, 1.0,
                                   True, t_process=0.0, rehearse=True,
                                   log=lambda m: None)
    assert result["correct"], result["checks"]
    assert result["metrics"]["answers_seen"]["value"] == side["answered"] > 0
    assert set(result["metrics"]) == {"answers_seen"}
