"""Dispatch-pipeline acceptance (PR 8): megabatched cross-shard dispatch
bit-identical to serial per-shard stepping (including under preemption,
hedging, and a mid-chunk kill), on-device merge == host merge ==
monolithic exact (hypothesis property + seeded in-suite twin), and
double-buffer determinism under a seeded chaos schedule."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # dev container: seeded twins below still run
    HAS_HYPOTHESIS = False

from repro.configs.base import VectorPoolConfig
from repro.core.scheduler import VectorRequest
from repro.core.trinity_pool import ShardedVectorPool
from repro.kernels.ops import (finalize_partial_topk, fold_partial_topk,
                               merge_partial_topk)
from repro.serving import sanitizer
from repro.serving.chaos import ChaosInjector, make_schedule
from repro.vector.dataset import make_dataset
from repro.vector.ref import exact_knn

SETTINGS = dict(max_examples=15, deadline=None)

ALL_ON = dict(megabatch_enabled=True, device_merge_enabled=True,
              double_buffer_enabled=True)
ALL_OFF = dict(megabatch_enabled=False, device_merge_enabled=False,
               double_buffer_enabled=False)


@pytest.fixture(scope="module")
def setup():
    db, queries = make_dataset(3000, 32, num_clusters=16, num_queries=96,
                               seed=1)
    return db, queries


def _cfg(**kw):
    base = dict(num_vectors=3000, dim=32, graph_degree=16, max_requests=16,
                top_m=32, parents_per_step=2, task_batch=2048,
                visited_slots=512, top_k=10, num_shards=4)
    base.update(kw)
    return VectorPoolConfig(**base)


def _snap(r):
    ids = None if r.result_ids is None else np.array(r.result_ids, copy=True)
    d = None if r.result_dists is None else np.array(r.result_dists,
                                                     copy=True)
    return ids, d


def _results(pool):
    return {r.rid: _snap(r) for r in pool.metrics.completed}


def _assert_same(a, b):
    assert set(a) == set(b), (len(a), len(b))
    for rid in a:
        for x, y in zip(a[rid], b[rid]):
            if x is None or y is None:
                assert x is y, rid
            else:
                np.testing.assert_array_equal(x, y, err_msg=str(rid))


def _drive(pool, queries, n=48, gap=1e-4, insert_every=0, chaos=None):
    """Submit a paced probe (+ optional insert) stream with optional
    mid-stream fault callbacks keyed by submission index."""
    rng = np.random.default_rng(5)
    t = 0.0
    for i in range(n):
        if insert_every and i % insert_every == 3:
            v = rng.standard_normal(pool.cfg.dim).astype(np.float32)
            pool.submit_insert(v, t_now=t)
        else:
            pool.submit(VectorRequest(i, "prefill", queries[i % len(queries)],
                                      t, t + 10.0))
        t += gap
        if chaos and i in chaos:
            pool.run_until(t)
            chaos[i](pool, t)
    pool.run_until(t + 5.0)
    return _results(pool)


# ---------------------------------------------------------------------------
# megabatched dispatch == serial per-shard stepping, bit for bit
# ---------------------------------------------------------------------------


def test_megabatch_bit_identical_plain(setup):
    db, queries = setup
    a = _drive(ShardedVectorPool(_cfg(**ALL_OFF), db, seed=0), queries)
    b = _drive(ShardedVectorPool(_cfg(**ALL_ON), db, seed=0), queries)
    _assert_same(a, b)


def test_megabatch_pallas_path_matches_jnp_path(setup):
    """The vmapped megabatch step on the distance kernel (the path a TPU
    selects by itself) returns what the jnp oracle path returns."""
    db, queries = setup
    a = _drive(ShardedVectorPool(_cfg(**ALL_ON), db, seed=0,
                                 use_pallas=False), queries)
    b = _drive(ShardedVectorPool(_cfg(**ALL_ON), db, seed=0,
                                 use_pallas=True), queries)
    assert set(a) == set(b), (len(a), len(b))
    for rid in a:
        np.testing.assert_array_equal(a[rid][0], b[rid][0], err_msg=str(rid))
        np.testing.assert_allclose(a[rid][1], b[rid][1], rtol=1e-5,
                                   atol=1e-5, err_msg=str(rid))


def test_megabatch_bit_identical_with_quiesced_inserts(setup):
    """Inserts mutate the searched corpus, so a probe's results depend on
    WHEN the broadcast lands relative to its chunks — and changing that
    timing is the whole point of the knobs. With inserts quiesced (pool
    drained around each one) every probe sees an identical corpus in both
    paths and full bit-identity must hold, including the post-insert
    gid translation of the new cache rows."""
    db, queries = setup
    rng = np.random.default_rng(5)
    vecs = rng.standard_normal((4, 32)).astype(np.float32)

    def run(knobs):
        pool = ShardedVectorPool(_cfg(**knobs), db, seed=0)
        t, rid = 0.0, 0
        for phase in range(4):
            for _ in range(8):
                pool.submit(VectorRequest(rid, "prefill",
                                          queries[rid % len(queries)],
                                          t, t + 10.0))
                rid += 1
                t += 1e-4
            pool.run_until(t + 5.0)  # drain, then mutate the corpus
            t += 5.0
            pool.submit_insert(vecs[phase], t_now=t)
            pool.run_until(t + 5.0)
            t += 5.0
        return _results(pool), pool

    a, pa = run(ALL_OFF)
    b, pb = run(ALL_ON)
    _assert_same(a, b)
    assert pa.metrics.inserts == pb.metrics.inserts == 4


def test_device_merge_matches_host_merge_with_concurrent_inserts(setup):
    """Device merge vs host merge at IDENTICAL sim timing (megabatch on
    in both, so chunk cohorts and insert broadcasts land at the same
    instants): a paced stream with mid-stream inserts must produce
    bit-identical results — this pins the fold's gid translation,
    including the insert-boundary chunk split (an insert completing
    earlier in the same chunk rewrites its shard's gid map before a
    later sibling is translated)."""
    db, queries = setup
    host = dict(megabatch_enabled=True, device_merge_enabled=False,
                double_buffer_enabled=False)
    dev = dict(megabatch_enabled=True, device_merge_enabled=True,
               double_buffer_enabled=False)
    a = _drive(ShardedVectorPool(_cfg(**host), db, seed=0), queries,
               insert_every=6)
    b = _drive(ShardedVectorPool(_cfg(**dev), db, seed=0), queries,
               insert_every=6)
    _assert_same(a, b)
    assert any(v[0] is None for v in a.values())  # inserts really ran


def test_megabatch_bit_identical_under_hedging(setup):
    """A hard straggler triggers hedged twins; the dedup (winner kept,
    loser dropped) must route identically through the grouped completion
    scan. rebalance_enabled shares per-shard engine seeds so both copies
    of a child compute the same ids."""
    db, queries = setup
    kw = dict(hedge_enabled=True, hedge_factor=4.0, rebalance_enabled=True)

    def run(knobs):
        pool = ShardedVectorPool(_cfg(**kw, **knobs), db,
                                 replicas_per_shard=2, seed=0)
        pool.set_slowdown(0, 200.0)
        out = _drive(pool, queries, n=32)
        return out, pool

    a, pa = run(ALL_OFF)
    b, pb = run(ALL_ON)
    _assert_same(a, b)
    assert pb.metrics.hedges >= 1 and pa.metrics.hedges >= 1


def test_megabatch_bit_identical_under_preemption(setup):
    """A tight-deadline decode probe preempts a prefill storm mid-chunk;
    eviction + checkpoint-resume must round-trip through the grouped
    state identically."""
    db, queries = setup
    kw = dict(decode_deadline_ms=3.0, prefill_deadline_ms=60.0,
              preempt_slack_ms=2.5, max_preemptions=2,
              preemption_enabled=True, num_shards=2, max_requests=8)

    def run(knobs):
        pool = ShardedVectorPool(_cfg(**kw, **knobs), db, seed=0)
        for r in range(len(pool.replicas)):
            pool.set_slowdown(r, 20.0)
        for i in range(16):
            pool.submit(VectorRequest(i, "prefill", queries[i], 0.0, 60e-3))
        pool.submit(VectorRequest(100, "decode", queries[32], 0.5e-3,
                                  3.5e-3))
        pool.run_until(0.1)
        return _results(pool), pool

    a, pa = run(ALL_OFF)
    b, pb = run(ALL_ON)
    _assert_same(a, b)
    assert pa.metrics.preemptions > 0 and pb.metrics.preemptions > 0


def test_megabatch_bit_identical_mid_chunk_kill(setup):
    """kill_replica lands between grouped chunks: the victim's lane is
    freed, its children restart (or rescue), and every request still
    completes bit-identically to the serial path under the same kill."""
    db, queries = setup
    kw = dict(rebalance_enabled=True, rescue_enabled=True)

    def kill(pool, t):
        victim = max(range(len(pool.replicas)),
                     key=lambda i: len(pool.replicas[i].in_flight))
        pool.kill_replica(victim)

    a = _drive(ShardedVectorPool(_cfg(**kw, **ALL_OFF), db,
                                 replicas_per_shard=2, seed=0),
               queries, chaos={20: kill})
    b = _drive(ShardedVectorPool(_cfg(**kw, **ALL_ON), db,
                                 replicas_per_shard=2, seed=0),
               queries, chaos={20: kill})
    _assert_same(a, b)


def test_knobs_off_is_legacy_serial_path(setup):
    """Knobs off must not even build the grouped engine — the legacy
    serial path stays byte-for-byte the code that ran before PR 8."""
    db, _ = setup
    pool = ShardedVectorPool(_cfg(**ALL_OFF), db, seed=0)
    assert pool._group is None and not pool._mega
    on = ShardedVectorPool(_cfg(**ALL_ON), db, seed=0)
    assert on._group is not None and on._mega and on._device_merge


# ---------------------------------------------------------------------------
# on-device merge == host merge_partial_topk == monolithic exact
# ---------------------------------------------------------------------------


def _check_device_merge_exact(n, s, k, seed):
    """For ANY random duplicate-free corpus, shard count and k: fold each
    shard's exhaustive local top-M through ``fold_partial_topk`` (with the
    local→global translation and the trailing −1 sentinel column) and
    finalize on device — the result must equal host
    ``merge_partial_topk`` over pre-translated lists AND the monolithic
    exact oracle, id for id."""
    k = min(k, n)
    m = max(k, 4)  # per-shard partial list length
    rng = np.random.default_rng(seed)
    db = rng.normal(size=(n, 8)).astype(np.float32)
    q = rng.normal(size=(8,)).astype(np.float32)
    owner = rng.integers(0, s, size=n)  # random (possibly empty) partition

    # per-shard exhaustive local top-m, padded with −1 like a real child
    locals_, trans_rows = [], []
    for sh in range(s):
        gids = np.nonzero(owner == sh)[0]
        d = np.sum((db[gids] - q) ** 2, axis=1) if len(gids) else \
            np.zeros((0,), np.float32)
        order = np.argsort(d, kind="stable")[:m]
        lid = np.full(m, -1, np.int32)
        ld = np.full(m, np.float32(np.inf), np.float32)
        lid[:len(order)] = order
        ld[:len(order)] = d[order]
        locals_.append((lid, ld))
        trans_rows.append(gids.astype(np.int32))

    # device path: one lane per shard, slot 0 holds the child's partial
    cap = 1
    while cap < max((len(r) for r in trans_rows), default=0) + 1:
        cap *= 2  # ≥1 trailing −1 sentinel column, as the pool builds it
    trans = np.full((s, cap), -1, np.int32)
    for sh, r in enumerate(trans_rows):
        trans[sh, :len(r)] = r
    top_ids = jnp.asarray(np.stack([l[0] for l in locals_])[:, None, :])
    top_dists = jnp.asarray(np.stack([l[1] for l in locals_])[:, None, :])
    buf_ids = jnp.full((1, s, m), -1, jnp.int32)
    buf_dists = jnp.full((1, s, m), jnp.float32(1e30))
    idx = jnp.arange(s, dtype=jnp.int32)
    zeros = jnp.zeros(s, jnp.int32)
    buf_ids, buf_dists = fold_partial_topk(
        buf_ids, buf_dists, top_ids, top_dists, jnp.asarray(trans),
        idx, zeros, zeros, idx)
    buf_ids2, _, dev_ids, dev_d = finalize_partial_topk(
        buf_ids, buf_dists, jnp.zeros(1, jnp.int32), k=k)
    dev_ids, dev_d = np.asarray(dev_ids[0]), np.asarray(dev_d[0])
    assert np.all(np.asarray(buf_ids2) == -1)  # row cleared for reuse

    # host path: pre-translate then merge_partial_topk
    host_in_ids = np.full((s, m), -1, np.int32)
    host_in_d = np.full((s, m), np.float32(np.inf))
    for sh, (lid, ld) in enumerate(locals_):
        ok = lid >= 0
        host_in_ids[sh, ok] = trans_rows[sh][lid[ok]]
        host_in_d[sh] = ld
    h_ids, h_d = merge_partial_topk(jnp.asarray(host_in_ids),
                                    jnp.asarray(host_in_d), k=k)
    np.testing.assert_array_equal(dev_ids, np.asarray(h_ids))
    np.testing.assert_array_equal(dev_d, np.asarray(h_d))

    # monolithic exact oracle (ids only where enough valid entries exist)
    true_ids, true_d = exact_knn(db, q[None, :], k)
    valid = dev_ids >= 0
    np.testing.assert_array_equal(dev_ids[valid], true_ids[0][valid])
    assert np.all(valid[:min(k, n)])
    np.testing.assert_allclose(dev_d[valid], true_d[0][valid],
                               rtol=1e-5, atol=1e-6)


if HAS_HYPOTHESIS:
    @settings(**SETTINGS)
    @given(n=st.integers(4, 60), s=st.integers(1, 6),
           k=st.integers(1, 12), seed=st.integers(0, 2**32 - 1))
    def test_device_merge_exact_hypothesis(n, s, k, seed):
        _check_device_merge_exact(n, s, k, seed)


def test_device_merge_exact_seeded():
    rng = np.random.default_rng(2024)
    for _ in range(15):
        _check_device_merge_exact(int(rng.integers(4, 60)),
                                  int(rng.integers(1, 6)),
                                  int(rng.integers(1, 12)),
                                  int(rng.integers(0, 2**31)))


# ---------------------------------------------------------------------------
# double-buffer determinism under seeded chaos
# ---------------------------------------------------------------------------


def test_double_buffer_deterministic_under_chaos(setup):
    """Same seeded fault schedule, two runs: identical completions (ids,
    dists, timestamps), zero lost, zero duplicated, sanitizer-clean —
    overlapping host scheduling with the in-flight chunk must not let a
    kill or straggle land mid-chunk."""
    db, queries = setup

    def run():
        pool = ShardedVectorPool(
            _cfg(rebalance_enabled=True, rescue_enabled=True,
                 sanitizer_enabled=True, **ALL_ON),
            db, replicas_per_shard=2, seed=0)
        san = sanitizer.attach(pool)
        for i in range(32):
            pool.submit(VectorRequest(i, "prefill", queries[i],
                                      i * 1e-4, i * 1e-4 + 0.05))
        sched = make_schedule(13, 0.0, 2e-3,
                              {"kill_replica": 800.0,
                               "straggle_replica": 800.0})
        inj = ChaosInjector(sched, seed=13)
        inj.run_pool(pool, 2.0)
        san.assert_clean()
        rids = sorted(r.rid for r in pool.metrics.completed)
        assert rids == list(range(32)), rids  # zero lost, zero duplicated
        return ({r.rid: _snap(r) for r in pool.metrics.completed},
                {r.rid: r.t_completed for r in pool.metrics.completed},
                inj.injected)

    res1, ts1, inj1 = run()
    res2, ts2, inj2 = run()
    assert inj1 == inj2 and inj1 >= 1
    _assert_same(res1, res2)
    assert ts1 == ts2
