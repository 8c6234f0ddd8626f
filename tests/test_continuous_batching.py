"""Trinity §3.2 continuous-batching engine: recall parity with the
per-request baseline, kernel-path equivalence, slot recycling."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import VectorPoolConfig
from repro.core.continuous_batching import ContinuousBatchingEngine
from repro.vector.cagra import search_batch
from repro.vector.dataset import make_dataset
from repro.vector.graph import make_cagra_graph
from repro.vector.ref import exact_knn, recall_at_k


@pytest.fixture(scope="module")
def setup():
    db, queries = make_dataset(3000, 64, num_clusters=24, num_queries=48,
                               seed=5)
    graph = make_cagra_graph(db, degree=16, seed=5)
    true_ids, _ = exact_knn(db, queries, 10)
    cfg = VectorPoolConfig(num_vectors=3000, dim=64, graph_degree=16,
                           max_requests=16, top_m=32, parents_per_step=2,
                           task_batch=1024, visited_slots=512, top_k=10)
    return cfg, db, graph, queries, true_ids


def _drain(engine, queries):
    results = {}
    qi = 0
    for _ in range(10_000):
        while engine.num_free > 0 and qi < len(queries):
            engine.admit(qi, queries[qi])
            qi += 1
        if engine.num_active == 0 and qi >= len(queries):
            break
        comps, _ = engine.step()
        for rid, ids, dists, ext in comps:
            results[rid] = ids
    return results


def test_recall_parity_with_per_request_baseline(setup):
    """Paper claim: continuous batching 'keeps search accuracy/recall
    behaviour intact'."""
    cfg, db, graph, queries, true_ids = setup
    eng = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False)
    results = _drain(eng, queries)
    found = np.stack([results[i] for i in range(len(queries))])
    r_cont = recall_at_k(found, true_ids)

    top_ids, _, _, _ = search_batch(
        jnp.asarray(db), jnp.asarray(graph), jnp.asarray(queries),
        top_m=cfg.top_m, p=cfg.parents_per_step, max_iters=64, num_entries=16)
    r_base = recall_at_k(np.asarray(top_ids)[:, :10], true_ids)
    assert r_cont > 0.85
    assert abs(r_cont - r_base) < 0.08, (r_cont, r_base)


def test_pallas_and_jnp_paths_identical(setup):
    cfg, db, graph, queries, _ = setup
    e1 = ContinuousBatchingEngine(cfg, db, graph, use_pallas=True, seed=9)
    e2 = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False, seed=9)
    for i in range(6):
        e1.admit(i, queries[i])
        e2.admit(i, queries[i])
    r1 = {rid: ids for rid, ids, _, _ in e1.run_to_completion()}
    r2 = {rid: ids for rid, ids, _, _ in e2.run_to_completion()}
    assert r1.keys() == r2.keys()
    for k in r1:
        np.testing.assert_array_equal(r1[k], r2[k])


def test_slots_recycled_and_new_arrivals_join_next_batch(setup):
    cfg, db, graph, queries, _ = setup
    eng = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False)
    for i in range(cfg.max_requests):
        eng.admit(i, queries[i])
    assert eng.num_free == 0
    done = []
    for _ in range(200):
        comps, _ = eng.step()
        done.extend(comps)
        if comps:
            break
    assert eng.num_free == len(done) > 0
    # a new arrival is admitted into a recycled slot and completes
    eng.admit(999, queries[20])
    assert eng.num_free == len(done) - 1
    out = eng.run_to_completion()
    assert any(rid == 999 for rid, *_ in out)


def test_admit_batch_matches_sequential_admits(setup):
    """admit_many (one vmapped dispatch) must be bit-identical to the
    per-request admit loop it replaces — including PRNG key order."""
    cfg, db, graph, queries, _ = setup
    e_seq = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False, seed=3)
    e_bat = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False, seed=3)
    for i in range(7):  # odd count exercises the power-of-two padding
        e_seq.admit(i, queries[i])
    e_bat.admit_batch([(i, queries[i]) for i in range(7)])
    for field in ("query_vecs", "top_ids", "top_dists", "expanded",
                  "visited", "active", "extends"):
        np.testing.assert_array_equal(
            np.asarray(getattr(e_seq.state, field)),
            np.asarray(getattr(e_bat.state, field)), err_msg=field)
    assert e_seq.free_slots == e_bat.free_slots
    assert e_seq.slot_request == e_bat.slot_request


def test_fused_multi_step_matches_raw_extend_step(setup):
    """extend_multi(K) must be bit-identical to K calls of the raw jitted
    extend_step (NOT routed through step()/step_multi, which themselves use
    the scan) — pins the scan-vs-plain-dispatch equivalence."""
    import jax

    from repro.core.continuous_batching import extend_multi, extend_step

    cfg, db, graph, queries, _ = setup
    e_raw = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False, seed=4)
    e_fus = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False, seed=4)
    n = 10
    e_raw.admit_batch([(i, queries[i]) for i in range(n)])
    e_fus.admit_batch([(i, queries[i]) for i in range(n)])

    K = 6
    kw = dict(p=cfg.parents_per_step, task_batch=cfg.task_batch,
              use_pallas=False, metric=cfg.metric,
              distance_mode=cfg.distance_mode)
    state = e_raw.state
    raw_completed, raw_tasks = [], []
    for _ in range(K):
        state, completed, tasks = extend_step(state, e_raw.db, e_raw.graph,
                                              **kw)
        raw_completed.append(np.asarray(completed))
        raw_tasks.append(int(tasks))
    fus_state, completed_k, tasks_k = extend_multi(
        e_fus.state, e_fus.db, e_fus.graph, num_steps=K, **kw)
    np.testing.assert_array_equal(np.stack(raw_completed),
                                  np.asarray(completed_k))
    np.testing.assert_array_equal(np.asarray(raw_tasks),
                                  np.asarray(tasks_k))
    for f_raw, f_fus in zip(jax.tree_util.tree_leaves(state),
                            jax.tree_util.tree_leaves(fus_state)):
        np.testing.assert_array_equal(np.asarray(f_raw), np.asarray(f_fus))


def test_fused_multi_step_matches_sequential_steps(setup):
    """step_multi(K) — one lax.scan dispatch — must produce bit-identical
    state and top-k results to K sequential step() calls, with completions
    attributed to the correct sub-step."""
    cfg, db, graph, queries, _ = setup
    e_seq = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False, seed=4)
    e_fus = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False, seed=4)
    n = 10
    e_seq.admit_batch([(i, queries[i]) for i in range(n)])
    e_fus.admit_batch([(i, queries[i]) for i in range(n)])

    K = 6
    seq_comps = []  # (rid, ids, dists, ext, substep)
    for i in range(K):
        comps, tasks = e_seq.step()
        seq_comps.extend((rid, ids, d, ext, i) for rid, ids, d, ext in comps)
    fus_comps, tasks_k = e_fus.step_multi(K)
    assert tasks_k.shape == (K,)

    for field in ("top_ids", "top_dists", "expanded", "visited", "active",
                  "extends"):
        np.testing.assert_array_equal(
            np.asarray(getattr(e_seq.state, field)),
            np.asarray(getattr(e_fus.state, field)), err_msg=field)

    seq_by_rid = {c[0]: c for c in seq_comps}
    fus_by_rid = {c[0]: c for c in fus_comps}
    assert seq_by_rid.keys() == fus_by_rid.keys()
    for rid in seq_by_rid:
        _, ids_s, d_s, ext_s, sub_s = seq_by_rid[rid]
        _, ids_f, d_f, ext_f, sub_f = fus_by_rid[rid]
        np.testing.assert_array_equal(ids_s, ids_f)  # bit-identical top-k
        np.testing.assert_array_equal(d_s, d_f)
        assert ext_s == ext_f and sub_s == sub_f

    # drains agree too (covers slot recycling after a fused chunk)
    r_seq = {rid: ids for rid, ids, _, _ in e_seq.run_to_completion()}
    r_fus = {rid: ids for rid, ids, _, _ in e_fus.run_to_completion()}
    assert r_seq.keys() == r_fus.keys()
    for rid in r_seq:
        np.testing.assert_array_equal(r_seq[rid], r_fus[rid])


def test_distance_modes_agree_through_engine(setup):
    """The slot-gather Pallas path and the matmul-onehot oracle path (jnp
    only: the one-hot form has no kernel) must yield equivalent search
    results end-to-end. The two formulas only agree to ~1e-4 in float32,
    so a distance tie at a selection boundary may legitimately swap ids —
    compare with tolerance, not bit-equality."""
    cfg, db, graph, queries, _ = setup
    import dataclasses
    cfg_oh = dataclasses.replace(cfg, distance_mode="matmul_onehot")
    e_sg = ContinuousBatchingEngine(cfg, db, graph, use_pallas=True, seed=11)
    e_oh = ContinuousBatchingEngine(cfg_oh, db, graph, use_pallas=False,
                                    seed=11)
    with pytest.raises(ValueError, match="no Pallas kernel"):
        ContinuousBatchingEngine(cfg_oh, db, graph, use_pallas=True).step()
    for i in range(6):
        e_sg.admit(i, queries[i])
        e_oh.admit(i, queries[i])
    r1 = {rid: (ids, d) for rid, ids, d, _ in e_sg.run_to_completion()}
    r2 = {rid: (ids, d) for rid, ids, d, _ in e_oh.run_to_completion()}
    assert r1.keys() == r2.keys()
    for k in r1:
        ids1, d1 = r1[k]
        ids2, d2 = r2[k]
        overlap = len(set(ids1.tolist()) & set(ids2.tolist())) / len(ids1)
        assert overlap >= 0.9, (k, ids1, ids2)
        np.testing.assert_allclose(d1, d2, rtol=1e-3, atol=1e-3)


def test_early_exit_no_infinite_loop(setup):
    cfg, db, graph, queries, _ = setup
    eng = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False)
    eng.admit(0, queries[0])
    out = eng.run_to_completion(max_steps=128)
    assert len(out) == 1
    assert eng.num_active == 0
    rid, ids, dists, ext = out[0]
    assert 0 < ext <= 128
    assert np.all(np.diff(dists) >= -1e-5)


def _load_ann_ref():
    """The benchmark's reference search (bench/ann_ref.py), by path: it
    derives entry points in ``jit`` over a uint32 rid vector, with nothing
    taken from the program."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "bench" / "ann_ref.py"
    spec = importlib.util.spec_from_file_location("_bench_ann_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_BIG_RIDS = [2**31 - 1, 2**31, 2**31 + 5, 4_100_000_000, 2**28]


@pytest.mark.parametrize("case", ["odd_batch", "rids_above_2_31",
                                  "slot_params"])
def test_admit_batch_matches_eager_key_derivation(setup, case):
    """``admit_many`` derives each entry key in-program; the seeded state
    must equal, field by field, one built from the eager derivation it
    replaced: ``fold_in(PRNGKey(seed), rid & 0x7FFFFFFF)`` per request,
    fed to ``_seed_request``."""
    import functools

    import jax

    from repro.core.continuous_batching import (SlotParams, _seed_request,
                                                init_engine_state)

    cfg, db, graph, queries, _ = setup
    seed = 21
    if case == "odd_batch":
        reqs = [(i, queries[i], None) for i in range(7)]
    elif case == "rids_above_2_31":
        reqs = [(rid, queries[i], None) for i, rid in enumerate(_BIG_RIDS)]
    else:
        reqs = [(40 + i, queries[i],
                 SlotParams(entry_lo=100 * i, entry_hi=100 * i + 500 + i,
                            budget=3 * i, top_k=5))
                for i in range(5)]
    eng = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False,
                                   seed=seed)
    slots = eng.admit_batch(reqs)

    ref = jax.tree_util.tree_map(np.array, init_engine_state(cfg))
    num_entries = min(16, cfg.top_m // 2)
    seed_one = jax.jit(functools.partial(
        _seed_request, top_m=cfg.top_m, visited_slots=cfg.visited_slots,
        num_entries=num_entries, metric=cfg.metric))
    base = jax.random.PRNGKey(seed)
    for slot, (rid, q, params) in zip(slots, reqs):
        params = params or SlotParams()
        hi = params.entry_hi or eng.corpus_n
        key = jax.random.fold_in(base, rid & 0x7FFFFFFF)
        ids, dists, visited = seed_one(eng.db, jnp.asarray(q), key,
                                       jnp.int32(params.entry_lo),
                                       jnp.int32(hi))
        ref.query_vecs[slot] = q
        ref.top_ids[slot] = ids
        ref.top_dists[slot] = dists
        ref.expanded[slot] = False
        ref.visited[slot] = visited
        ref.active[slot] = True
        ref.extends[slot] = 0
        ref.budget[slot] = params.budget
    for field in ("query_vecs", "top_ids", "top_dists", "expanded",
                  "visited", "active", "extends", "budget"):
        np.testing.assert_array_equal(np.asarray(getattr(eng.state, field)),
                                      getattr(ref, field), err_msg=field)
    if case == "slot_params":
        assert eng.slot_topk == {s: 5 for s in slots}


def test_admit_batch_entry_points_match_bench_reference(setup):
    """The benchmark's ``correct`` check rests on this agreement: the
    engine's seeded entry ids equal ``bench/ann_ref.entry_points`` for the
    same seed and rids, masked rids above 2**31 included."""
    cfg, db, graph, queries, _ = setup
    ann_ref = _load_ann_ref()
    seed = 4_100_000_021
    rids = [0, 5, 12345] + _BIG_RIDS
    eng = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False,
                                   seed=seed)
    slots = eng.admit_batch([(rid, queries[i]) for i, rid in enumerate(rids)])
    num = min(16, cfg.top_m // 2)
    want = ann_ref.entry_points(seed, np.asarray(rids, np.int64),
                                eng.corpus_n, num)
    got = np.asarray(eng.state.top_ids)[slots, :num]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("B", [1, 5])
def test_admit_batch_is_one_program(setup, monkeypatch, B):
    """After a warm call, a flush of B requests makes exactly one call into
    the jitted ``admit_many`` and runs no eager key fold, stack or
    host-to-device ``asarray`` before it."""
    import jax

    from repro.core import continuous_batching as cb

    cfg, db, graph, queries, _ = setup
    eng = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False)
    eng.admit_batch([(100 + i, queries[i]) for i in range(B)])  # compile
    calls = {}

    def spy(owner, name):
        real = getattr(owner, name)

        def wrapped(*args, **kwargs):
            calls[name] = calls.get(name, 0) + 1
            return real(*args, **kwargs)

        monkeypatch.setattr(owner, name, wrapped)

    spy(cb, "admit_many")
    spy(jax.random, "fold_in")
    spy(jnp, "stack")
    spy(jnp, "asarray")
    slots = eng.admit_batch([(200 + i, queries[B + i]) for i in range(B)])
    assert calls == {"admit_many": 1}
    assert len(slots) == B and eng.num_active == 2 * B


@pytest.fixture(scope="module")
def wide(setup):
    """R = 64, p = 2, D = 16 and a 2048-task batch: slot buckets 8, 16, 32
    and 64. Queries are corpus rows with a little noise, 160 of them."""
    import dataclasses

    cfg, db, graph, _, _ = setup
    cfg = dataclasses.replace(cfg, max_requests=64, task_batch=2048)
    rng = np.random.default_rng(17)
    rows = db[rng.choice(len(db), 160, replace=False)]
    queries = (rows + 0.05 * rng.standard_normal(rows.shape)).astype(
        np.float32)
    return cfg, db, graph, queries


def _active_counts_agree(eng):
    assert eng.num_active == int(np.asarray(eng.state.active).sum())


@pytest.mark.parametrize("occupancy,use_pallas", [
    (1, False), (8, False), (9, False), (16, False), (17, False),
    (33, False), (64, False), (9, True)])
def test_slot_buckets_bit_identical_to_full_step(wide, occupancy,
                                                 use_pallas):
    """``step_multi`` over the smallest slot bucket that holds the active
    slots returns what the 64-slot ``extend_multi`` returns: ids, dists,
    extend counts, sub-steps and task counts, through completions inside
    a chunk, exhausted budgets, and a preemption resumed two chunks later.
    After every step the host's active count is the device's."""
    from repro.core.continuous_batching import SlotParams, slot_buckets

    cfg, db, graph, queries = wide
    assert slot_buckets(cfg) == (8, 16, 32, 64)
    bkt = ContinuousBatchingEngine(cfg, db, graph, use_pallas=use_pallas,
                                   seed=21)
    full = ContinuousBatchingEngine(cfg, db, graph, use_pallas=use_pallas,
                                    seed=21)
    full.slot_buckets = (cfg.max_requests,)
    engines = (bkt, full)
    next_rid = [0]

    def top_up(n):
        reqs = []
        while bkt.num_active + len(reqs) < n and next_rid[0] < len(queries):
            rid = next_rid[0]
            params = SlotParams(budget=3) if rid % 5 == 0 else None
            reqs.append((rid, queries[rid], params))
            next_rid[0] += 1
        for eng in engines:
            eng.admit_batch(reqs)

    top_up(occupancy)
    parked, done, dispatched = [], [], set()
    for chunk in range(200):
        if bkt.num_active == 0 and not parked:
            break
        active = bkt.num_active
        got, tasks_b = bkt.step_multi()
        want, tasks_f = full.step_multi()
        assert bkt.dispatched_slots == min(
            b for b in bkt.slot_buckets if b >= active)
        assert full.dispatched_slots == cfg.max_requests
        dispatched.add(bkt.dispatched_slots)
        np.testing.assert_array_equal(tasks_b, tasks_f)
        assert [c[0] for c in got] == [c[0] for c in want]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g[1], w[1])  # ids
            np.testing.assert_array_equal(g[2], w[2])  # dists
            assert g[3:] == w[3:]  # extends, substep
        done.extend(got)
        for eng in engines:
            _active_counts_agree(eng)
        if chunk == 1:
            victims = sorted(bkt.slot_request.values())[:2]
            ckpts = [eng.preempt(victims) for eng in engines]
            parked = ckpts[0]
            assert [r for r, _ in ckpts[1]] == [r for r, _ in parked]
        elif chunk == 3:
            for eng in engines:
                eng.resume_batch(parked)
            parked = []
        if chunk < 6:
            top_up(occupancy - len(parked))
        for eng in engines:
            _active_counts_agree(eng)
    assert bkt.num_active == 0 and not parked
    K = cfg.extend_chunk
    assert any(c[4] < K - 1 for c in done)  # completed mid-chunk
    assert any(c[0] % 5 == 0 and c[3] == 3 for c in done)  # budget spent
    # the drain ends in the smallest bucket
    assert min(dispatched) == 8 and max(dispatched) >= occupancy


def test_bucket_is_smallest_holding_active_and_compiles_once(wide,
                                                             monkeypatch):
    """Through every occupancy from 1 to R, the engine dispatches the
    smallest bucket that holds ``num_active`` (R when full), and
    ``extend_multi`` compiles one program per bucket and chunk length."""
    from repro.core import continuous_batching as cb

    cfg, db, graph, queries = wide
    eng = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False, seed=5)
    real = cb.extend_multi
    seen = []

    def spy(*args, **kwargs):
        seen.append((kwargs["num_steps"], kwargs["num_slots"]))
        return real(*args, **kwargs)

    monkeypatch.setattr(cb, "extend_multi", spy)
    real.clear_cache()
    rid = 0
    for n in range(1, cfg.max_requests + 1):
        reqs = []
        while eng.num_active + len(reqs) < n:
            reqs.append((rid, queries[rid % len(queries)]))
            rid += 1
        eng.admit_batch(reqs)
        assert eng.num_active == n
        eng.step_multi()
        assert seen[-1][1] == min(b for b in (8, 16, 32, 64) if b >= n)
    assert seen[-1][1] == cfg.max_requests
    assert set(seen) == {(cfg.extend_chunk, b) for b in (8, 16, 32, 64)}
    assert real._cache_size() == len(set(seen))
