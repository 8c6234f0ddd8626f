"""The pool's host spans and queue counters (``core/tracing.py``): the
tracer's nesting and totals, the spans a served pool writes into a
profiler trace (read back with ``ProfileData``), the first-admission
queue-wait counters, and answers unchanged by a recording profiler."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import VectorPoolConfig
from repro.core import tracing
from repro.core.scheduler import VectorRequest
from repro.core.trinity_pool import ShardedVectorPool, VectorPool
from repro.vector.dataset import make_dataset
from repro.vector.graph import make_cagra_graph


@pytest.fixture(scope="module")
def setup():
    db, queries = make_dataset(1500, 32, num_clusters=8, num_queries=48,
                               seed=3)
    graph = make_cagra_graph(db, degree=16, seed=3)
    cfg = VectorPoolConfig(num_vectors=1500, dim=32, graph_degree=16,
                           max_requests=8, top_m=32, parents_per_step=2,
                           task_batch=1024, visited_slots=512, top_k=10)
    return cfg, db, graph, queries


def _serve(pool, queries, n=24, gap=2e-4):
    """A paced prefill/decode stream, one ``run_until`` per arrival."""
    t = 0.0
    for i in range(n):
        kind = "prefill" if i % 3 == 0 else "decode"
        pool.submit(VectorRequest(i, kind, queries[i % len(queries)], t,
                                  t + 0.1))
        t += gap
        pool.run_until(t)
    pool.run_until(t + 1.0)
    return {r.rid: (np.array(r.result_ids), np.array(r.result_dists))
            for r in pool.metrics.completed}


def _traced(tmp_path, fn):
    """``fn()`` under a profiler session; (its result, the trinity.*
    spans of the trace as (name, start_ns, end_ns, line, stats))."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              (pl.name, ln.name), dict(e.stats))
             for pl in ProfileData.from_file(path).planes
             for ln in pl.lines for e in ln.events
             if e.name.startswith(tracing.PREFIX)]
    return out, spans


def _nest_in_run_until(spans):
    calls = [s for s in spans if s[0] == "trinity.run_until"]
    assert calls
    for name, s, e, line, _ in spans:
        if name != "trinity.run_until":
            assert any(c[3] == line and c[1] <= s and e <= c[2]
                       for c in calls), name


def test_tracer_nesting_counts_and_call_record(monkeypatch):
    clock = [0.0]
    monkeypatch.setattr(tracing.time, "perf_counter", lambda: clock[0])
    tr = tracing.Tracer()

    def tick(s):
        clock[0] += s

    with tr.span("run_until"):
        tick(1.0)
        with tr.span("schedule"):
            tick(2.0)
            with tr.span("admit", n=3):
                tick(4.0)
        with tr.span("sync"):
            tick(8.0)
        with tr.span("sync"):
            tick(16.0)
    assert {k: v.count for k, v in tr.stats.items()} == {
        "run_until": 1, "schedule": 1, "admit": 1, "sync": 2}
    assert tr.stats["run_until"].total_s == 31.0
    assert tr.stats["schedule"].total_s == 6.0
    assert tr.stats["sync"].total_s == 24.0
    assert tr.stats["sync"].max_s == 16.0
    # self time: the part of each span outside the spans nested in it
    assert dict(tr.call) == {"run_until": 1.0, "schedule": 2.0,
                             "admit": 4.0, "sync": 24.0}
    assert not tr.stack
    # no profiler session: nothing recorded, no annotation made
    assert not tr.recorded
    # the next call starts a fresh record
    with tr.span("run_until"):
        tick(0.5)
    assert dict(tr.call) == {"run_until": 0.5}
    assert tr.stats["run_until"].count == 2


def test_spans_in_a_profiler_trace(setup, tmp_path):
    cfg, db, graph, queries = setup
    _serve(VectorPool(cfg, db, graph, use_pallas=False, seed=0), queries)
    pool = VectorPool(cfg, db, graph, use_pallas=False, seed=0)
    before = {k: v.count for k, v in tracing.TRACER.recorded.items()}
    _, spans = _traced(tmp_path, lambda: _serve(pool, queries))
    names = {s[0] for s in spans}
    assert names >= {"trinity." + n for n in (
        "run_until", "schedule", "admit", "dispatch", "sync", "collect",
        "complete")}
    _nest_in_run_until(spans)
    count = lambda n: sum(s[0] == "trinity." + n for s in spans)
    chunks = pool.metrics.extend_steps // cfg.extend_chunk
    assert chunks > 0
    assert count("dispatch") == count("sync") == chunks
    # the tracer's record of the spans a session held is the trace's own
    after = tracing.TRACER.recorded
    for n in ("run_until", "dispatch", "sync", "admit"):
        assert after[n].count - before.get(n, 0) == count(n)
    # admissions carry their count and, while recording, their rids
    admits = [s[4] for s in spans if s[0] == "trinity.admit"]
    assert sum(a["n"] for a in admits) == len(pool.metrics.completed)
    rids = sorted(int(r) for a in admits for r in str(a["rids"]).split())
    assert rids == sorted(r.rid for r in pool.metrics.completed)


def test_dispatch_span_names_its_slot_bucket(setup, tmp_path):
    """Each ``trinity.dispatch`` carries the slot bucket it ran over, the
    smallest that holds its active slots, and ``tasks_capacity`` counts
    those buckets' task arrays."""
    from repro.core.continuous_batching import slot_buckets

    cfg, db, graph, queries = setup
    buckets = slot_buckets(cfg)
    assert buckets == (2, 4, 8)
    serve = lambda pool: _serve(pool, queries, n=30, gap=2e-5)
    serve(VectorPool(cfg, db, graph, use_pallas=False, seed=0))
    pool = VectorPool(cfg, db, graph, use_pallas=False, seed=0)
    _, spans = _traced(tmp_path, lambda: serve(pool))
    args = [s[4] for s in spans if s[0] == "trinity.dispatch"]
    assert args
    for a in args:
        assert a["slots"] == min(b for b in buckets if b >= a["active"])
    assert len({a["slots"] for a in args}) > 1
    R, k = cfg.max_requests, cfg.extend_chunk
    assert pool.metrics.tasks_capacity == sum(
        k * cfg.task_batch * a["slots"] // R for a in args)


def test_spans_of_a_grouped_chunk(setup, tmp_path):
    _, db, _, queries = setup
    cfg = VectorPoolConfig(num_vectors=1500, dim=32, graph_degree=16,
                           max_requests=8, top_m=32, parents_per_step=2,
                           task_batch=1024, visited_slots=512, top_k=10,
                           num_shards=2, megabatch_enabled=True,
                           device_merge_enabled=True,
                           double_buffer_enabled=True)
    _serve(ShardedVectorPool(cfg, db, seed=0), queries, n=4)
    pool = ShardedVectorPool(cfg, db, seed=0)
    done, spans = _traced(tmp_path, lambda: _serve(pool, queries, n=4))
    assert len(done) == 4
    names = {s[0] for s in spans}
    assert names >= {"trinity." + n for n in (
        "run_until", "schedule", "admit", "dispatch", "sync", "collect",
        "complete")}
    _nest_in_run_until(spans)
    count = lambda n: sum(s[0] == "trinity." + n for s in spans)
    # one grouped dispatch, and one wait for its masks, per chunk
    assert count("dispatch") == count("sync") > 0
    # every member steps K extends in each grouped chunk it joins
    assert pool.metrics.extend_steps >= count("dispatch") * cfg.extend_chunk


def test_queue_wait_counters(setup):
    cfg, db, graph, queries = setup
    pool = VectorPool(cfg, db, graph, use_pallas=False, seed=0)
    _serve(pool, queries, n=30, gap=5e-5)
    done = pool.metrics.completed
    assert len(done) == 30
    m = pool.metrics
    for stage in ("prefill", "decode"):
        reqs = [r for r in done if r.kind == stage]
        assert m.admitted[stage] == len(reqs)
        assert m.queue_wait_s[stage] == pytest.approx(
            sum(r.t_admitted - r.t_arrival for r in reqs), abs=1e-12)
    # the burst queues: some probes waited for a slot
    assert sum(m.queue_wait_s.values()) > 0


def test_answers_unchanged_by_the_profiler(setup, tmp_path):
    cfg, db, graph, queries = setup
    plain = _serve(VectorPool(cfg, db, graph, use_pallas=False, seed=0),
                   queries)
    traced, _ = _traced(tmp_path, lambda: _serve(
        VectorPool(cfg, db, graph, use_pallas=False, seed=0), queries))
    assert plain.keys() == traced.keys() and plain
    for rid in plain:
        for a, b in zip(plain[rid], traced[rid]):
            np.testing.assert_array_equal(a, b)
