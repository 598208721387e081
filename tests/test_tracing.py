"""Spans of GraphServe's request and dispatch paths (runtime/tracing.py):
each dispatch tiled by its four stages under one serial, none of them a
copy to the device, every answered
request's queue span parented to the dispatch that answered it,
`finished_s` after the copy back, the bounded ring under concurrent
recording, the sharded path's names, the scheduler's host stage, and the
live spans' profiler annotations on the ring's own clock."""
import glob
import os
import sys
import threading

import pytest

import jax

from clockwork import FakeClock

from repro.core.graph import BucketLadder
from repro.core.models import GNNConfig
from repro.data.graphs import clustered_like, planetoid_like
from repro.runtime.clock import WALL
from repro.runtime.gnn_server import GraphServe, GraphServeConfig
from repro.runtime.scheduler import PipelineConfig
from repro.runtime.tracing import PREFIX, Tracer, default_tracer

IN_FEATS, CLASSES, BUCKET, SLOTS = 16, 4, 128, 2
STAGES = ("dispatch.stack", "dispatch.operands", "dispatch.device",
          "dispatch.d2h")
LIVE = ("host", "dispatch") + STAGES


class TickingClock(FakeClock):
    """Virtual time that also moves 1 us at every read, so the order of
    the stamps shows in their values."""

    def now(self) -> float:
        self.advance(1e-6)
        return super().now()


def _graph(n, seed=0):
    return planetoid_like(num_nodes=n, num_edges=3 * n, num_feats=IN_FEATS,
                          num_classes=CLASSES, seed=seed, train_per_class=2)


def _cfg():
    return GNNConfig(kind="gcn", in_feats=IN_FEATS, hidden=16,
                     num_classes=CLASSES)


@pytest.fixture(scope="module")
def warm_engine():
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(BUCKET,)),
                          batch_slots=SLOTS, return_logits=True)
    eng = GraphServe(sc, seed=0)
    eng.register_model("gcn", _cfg())
    eng.warmup()
    eng.gids = [eng.attach(_graph(60 + 10 * i, seed=i), model="gcn")
                for i in range(3)]
    return eng


@pytest.fixture
def engine(warm_engine):
    """The warm engine with a fresh ring and a fresh ticking clock."""
    warm_engine.tracer = Tracer()
    warm_engine.clock = TickingClock(default_batch_s=0.003)
    warm_engine.finished.clear()
    return warm_engine


def _by_serial(tracer):
    """Every `dispatch` and `dispatch.*` span, by serial."""
    out = {}
    for s in tracer.spans():
        if s.name == "dispatch" or s.name.startswith("dispatch."):
            out.setdefault(s.id, {})[s.name] = s
    return out


def _filled(tracer):
    """Requests answered per dispatch serial, from their queue spans."""
    out = {}
    for q in tracer.spans("request.queue"):
        out[q.parent] = out.get(q.parent, 0) + 1
    return out


def _serve(eng, n):
    """n queries over the tenants, submitted a millisecond apart."""
    uids = []
    for i in range(n):
        uids.append(eng.query(eng.gids[i % len(eng.gids)]))
        eng.clock.advance(1e-3)
    eng.run()
    return uids


def test_dispatch_spans_tile_the_dispatch_in_order(engine):
    _serve(engine, 3)                       # every tenant's features cached
    engine.tracer = Tracer()
    batches, sent = engine.metrics["batches"], \
        engine.metrics["feature_bytes_h2d"]
    _serve(engine, 5)                       # 3 dispatches: 2 + 2 + 1
    dispatches = _by_serial(engine.tracer)
    assert len(dispatches) == engine.metrics["batches"] - batches == 3
    # CacheG hits: nothing crosses to the device, and no dispatch.h2d
    assert engine.metrics["feature_bytes_h2d"] == sent
    for serial, spans in dispatches.items():
        assert set(spans) == {"dispatch", *STAGES}
        assert "dispatch.h2d" not in spans
        d = spans["dispatch"]
        t = d.start
        for name in STAGES:
            s = spans[name]
            assert s.parent is None and s.id == serial
            assert t <= s.start < s.end <= d.end, name
            t = s.end
        assert all(s.attrs is None for s in spans.values())
        # the fake clock's scripted batch cost lands in the device stage
        assert spans["dispatch.device"].end - spans["dispatch.device"].start \
            >= 0.003
    filled = _filled(engine.tracer)
    assert [filled[s] for s in sorted(dispatches)] == [2, 2, 1]


def test_device_busy_and_latency_read_the_span_stamps(engine):
    busy0 = engine.metrics["device_busy_s"]
    _serve(engine, 4)
    dispatches = _by_serial(engine.tracer).values()
    busy = sum(d["dispatch.d2h"].end - d["dispatch"].start
               for d in dispatches)
    assert engine.metrics["device_busy_s"] - busy0 == pytest.approx(busy)
    last = max(d["dispatch.d2h"].end for d in dispatches)
    assert engine.metrics["last_finish_s"] == last


def test_answered_requests_have_queue_spans(engine):
    uids = _serve(engine, 5)
    dispatches = _by_serial(engine.tracer)
    queue = {s.id: s for s in engine.tracer.spans("request.queue")}
    assert set(queue) == set(uids)
    answered = {r.uid: r for r in engine.finished}
    per_dispatch = {}
    for uid in uids:
        r, q = answered[uid], queue[uid]
        assert q.parent in dispatches
        d = dispatches[q.parent]
        assert q.start == r.submitted_s
        assert q.end == d["dispatch"].start
        per_dispatch[q.parent] = per_dispatch.get(q.parent, 0) + 1
    assert [per_dispatch[s] for s in sorted(dispatches)] == [2, 2, 1]


def test_finished_after_the_copy_back(engine):
    _serve(engine, 3)
    dispatches = _by_serial(engine.tracer)
    parent = {s.id: s.parent for s in engine.tracer.spans("request.queue")}
    for r in engine.finished:
        d2h = dispatches[parent[r.uid]]["dispatch.d2h"]
        assert r.done and r.finished_s >= d2h.end > d2h.start
        assert r.finished_s - r.submitted_s == pytest.approx(
            d2h.end - r.submitted_s)


def test_scheduler_host_stage_is_a_span(engine):
    with engine.scheduler(PipelineConfig(deterministic=True)) as sch:
        for i in range(4):
            sch.query(engine.gids[i % 3])
        done = sch.drain()
    host = engine.tracer.spans("host")
    assert sorted(s.id for s in host) == sorted(r.uid for r in done)
    assert all(s.end > s.start for s in host)
    assert sch.metrics["host_busy_s"] == pytest.approx(
        sum(s.end - s.start for s in host))


def test_ring_keeps_only_its_capacity():
    tr = Tracer(capacity=5)
    for i in range(12):
        tr.record("dispatch", float(i), i + 0.5, i)
    assert [s.id for s in tr.spans()] == [7, 8, 9, 10, 11]
    with tr.span("host", FakeClock(start=3.0), 99):
        pass
    assert [s.id for s in tr.spans()] == [8, 9, 10, 11, 99]
    assert tr.spans("host")[0].start == 3.0
    tr = Tracer()
    for i in range((1 << 16) + 3):
        tr.record("request.queue", 0.0, 1.0, i)
    assert [s.id for s in tr.spans()[:2]] == [3, 4]
    assert len(tr.spans()) == 1 << 16
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_threads_recording_at_once_lose_no_span():
    """More recording threads than cores, switching every microsecond:
    every live and ring-only span lands once, and a full ring holds
    exactly its capacity."""
    n_threads, per = 4 * (os.cpu_count() or 1), 400
    ids = set(range(n_threads * per))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for capacity in (2 * len(ids), 1000):
            tr, clock = Tracer(capacity=capacity), FakeClock()

            def work(t):
                for i in range(t * per, (t + 1) * per):
                    with tr.span("host", clock, i):
                        pass
                    tr.record("request.queue", 0.0, 1.0, i)

            threads = [threading.Thread(target=work, args=(t,))
                       for t in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            if capacity == 2 * len(ids):
                assert sorted(s.id for s in tr.spans("host")) == sorted(ids)
                assert sorted(s.id for s in tr.spans("request.queue")) \
                    == sorted(ids)
            else:
                assert len(tr.spans()) == capacity
    finally:
        sys.setswitchinterval(old)


def test_engine_records_into_the_default_tracer_unless_given_one():
    assert GraphServe().tracer is default_tracer()
    mine = Tracer()
    assert GraphServe(tracer=mine).tracer is mine


def test_sharded_path_records_the_same_names():
    """The batched path's names, but for `dispatch.stack`: the shard
    features are on the device since the host stage, and their replica
    stack is part of `dispatch.operands`."""
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(BUCKET,)),
                          batch_slots=SLOTS, shard_counts=(2,),
                          return_logits=True)
    tr = Tracer()
    eng = GraphServe(sc, seed=0, clock=TickingClock(), tracer=tr)
    eng.register_model("gcn", _cfg())
    g = clustered_like(num_nodes=200, num_feats=IN_FEATS,
                       num_classes=CLASSES, within_density=0.05,
                       cross_frac=0.1, seed=10)
    gid = eng.attach(g, model="gcn")
    uid = eng.query(gid)
    eng.run()
    assert eng.metrics["sharded_batches"] == 1
    (serial, spans), = _by_serial(tr).items()
    stages = ("dispatch.operands", "dispatch.device", "dispatch.d2h")
    assert set(spans) == {"dispatch", *stages}
    t = spans["dispatch"].start
    for name in stages:
        assert t <= spans[name].start < spans[name].end <= \
            spans["dispatch"].end, name
        t = spans[name].end
    (q,) = tr.spans("request.queue")
    assert (q.id, q.parent, q.end) == (uid, serial, spans["dispatch"].start)
    assert eng.finished[0].finished_s >= spans["dispatch.d2h"].end


def test_profiler_sees_every_live_span_on_the_ring_clock(warm_engine,
                                                         tmp_path):
    """Under a CPU profile, every live span is a `graphserve.*` host event,
    and its start differs from the ring's by one offset for all of them:
    the ring and the device trace share a clock."""
    from jax.profiler import ProfileData
    eng = warm_engine
    eng.tracer = tr = Tracer()
    eng.clock = WALL
    jax.profiler.start_trace(str(tmp_path))
    try:
        with eng.scheduler(PipelineConfig(host_workers=2,
                                          window_ms=1.0)) as sch:
            for i in range(6):
                sch.query(eng.gids[i % 3])
            sch.drain(timeout=120)
        for i in range(3):
            eng.query(eng.gids[i])
        eng.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    events.setdefault(e.name[len(PREFIX):], []).append(
                        e.start_ns * 1e-9)
    ring = [s for s in tr.spans() if s.name in LIVE]
    assert {s.name for s in ring} == set(LIVE)
    assert set(events) == set(LIVE)
    offsets = []
    for name in LIVE:
        mine = sorted(s.start for s in ring if s.name == name)
        theirs = sorted(events[name])
        assert len(theirs) == len(mine), name
        offsets += [b - a for a, b in zip(mine, theirs)]
    assert max(offsets) - min(offsets) < 1e-3, (min(offsets), max(offsets))


def _edge_engine(monkeypatch, tracer):
    """A three-layer SAGE engine whose bucket only the edge-list form holds
    (DESIGN.md §16): 1 MiB of device memory steered here."""
    from repro.runtime import gnn_server
    monkeypatch.setattr(gnn_server, "device_memory_bytes", lambda: 1 << 20)
    eng = GraphServe(GraphServeConfig(ladder=BucketLadder(buckets=(256,)),
                                      batch_slots=SLOTS), tracer=tracer)
    eng.register_model("sage", GNNConfig(
        kind="sage", in_feats=IN_FEATS, hidden=32, num_classes=CLASSES,
        num_layers=3, batch_norm=True, max_neighbors=None),
        agg_backend="auto")
    return eng


def test_edge_operands_span_once_per_miss(monkeypatch):
    tr = Tracer()
    eng = _edge_engine(monkeypatch, tr)
    gid = eng.attach(_graph(200, seed=1), model="sage")
    built = tr.spans("operands.edges")
    assert [s.id for s in built] == [gid]                 # built at attach
    for _ in range(3):
        eng.query(gid)
    eng.run()
    assert len(tr.spans("operands.edges")) == 1           # hits build none
    assert eng.metrics["operand_cache_hits"] == 3
    g2 = _graph(220, seed=2)
    eng.update(gid, g2.edge_index, g2.num_nodes, g2.features)
    eng.query(gid)
    eng.query(gid)
    eng.run()
    assert len(tr.spans("operands.edges")) == 2           # one miss
    assert eng.metrics["operand_cache_misses"] == 1


def test_edge_operand_bytes_follow_attach_and_detach(monkeypatch):
    eng = _edge_engine(monkeypatch, Tracer())
    assert eng.summary()["edge_operand_bytes"] == 0
    gids = [eng.attach(_graph(200 + 10 * i, seed=i), model="sage")
            for i in range(2)]
    both = eng.summary()["edge_operand_bytes"]
    assert both > 0
    eng.detach(gids[0])
    assert 0 < eng.summary()["edge_operand_bytes"] < both
    eng.detach(gids[1])
    assert eng.summary()["edge_operand_bytes"] == 0


def test_edges_dispatched_counts_real_edges_once_per_layer(monkeypatch):
    tr = Tracer()
    eng = _edge_engine(monkeypatch, tr)
    gs = [_graph(200, seed=3), _graph(210, seed=4)]
    gids = [eng.attach(g, model="sage") for g in gs]
    for gid in gids:
        eng.query(gid)
    eng.run()
    edges = sum(g.num_edges for g in gs)
    assert eng.metrics["batches"] == 1
    assert eng.metrics["edges_dispatched"] == 3 * edges
    (device,) = tr.spans("dispatch.device")
    assert device.attrs == {"nodes": 410, "edges": edges}
