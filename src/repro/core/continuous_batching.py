"""Trinity §3.2: continuous batching for graph vector search.

One *extend* step on the graph is the scheduling unit. The engine keeps a
fixed array of request slots with compact device-side state (topM ids +
dists, expanded flags, visited hash table). Every engine iteration:

  1. per active slot: select ≤ p unexpanded parents from topM,
  2. read D neighbours per parent, filter via the visited table,
  3. emit survivors into ONE global cross-request task array (fixed shape
     ``task_batch``; short batches are rounded up with masked dummies),
  4. evaluate all tasks with a single fixed-shape distance operator — the
     Pallas kernel (kernels/distance.py) on TPU, its jnp oracle on CPU,
  5. scatter (id, dist) back per slot, merge into topM, mark parents
     expanded,
  6. slots whose topM gained no unexpanded candidate are *converged*: they
     exit immediately and free their slot; new arrivals join the very next
     distance batch.

The whole step is one jitted fixed-shape function (the CUDA-graph analogue)
— state in, state out — per *slot bucket*: ``extend_multi`` runs over a
sub-state of ``Rb`` slots, the active ones first, with a task array of
``task_batch · Rb / R``, and scatters it back. The buckets are the powers
of two from the smallest slot count whose task array fills one kernel task
block up to R (``slot_buckets``); the engine passes the smallest bucket
that holds ``num_active``. Every slot's math is its own, so answers, extend
and task counts are bit-identical to the R-slot step, and only
O(log R) shapes ever compile.

Fused multi-extend stepping (the dispatch-overhead fix): the host loop used
to re-cross the host-device boundary every step (one jitted dispatch + a
``completed`` readback + two scalar syncs per extend). ``extend_multi`` runs
K = ``VectorPoolConfig.extend_chunk`` extend steps device-side under one
``lax.scan`` dispatch and returns *stacked* per-step completion masks
(K, R) and task counts (K,), so the host syncs once per K steps. A request
completing at sub-step i goes inactive for the remaining K−i−1 sub-steps
(its slot state is untouched until re-admission), so the fused path is
bit-identical to K sequential ``extend_step`` calls — asserted in
tests/test_continuous_batching.py. Admission is likewise batched:
``admit_many`` seeds a whole scheduler batch in ONE jitted vmapped dispatch
(batch padded to a power-of-two bucket by replicating row 0 — duplicate
scatters write identical values), fed from two host buffers and deriving
each request's entry key itself, so no eager JAX op precedes it. Parent
selection uses ``jax.lax.top_k`` on negated rank (O(M·p)) instead of a
full argsort (O(M log M)); ties break to the lower index in both, so
selection is unchanged.

Per-slot search params (retrieval-class heterogeneity): each slot carries
its own entry-point range (``entry_lo``/``entry_hi`` — index segment the
seeding samples from), extend budget (``budget``: forced completion once a
search has consumed that many extends, 0 = run to natural convergence) and
top-k truncation (host-side, applied when the completion is collected).
All of it rides the existing fixed kernel shapes: the budget is one extra
(R,) int32 column in the engine state, the entry range only parameterises
admission seeding (traced scalars — no recompile per class), and top-k
never reaches the device. Defaults reproduce the old single-class engine
bit-identically.

Stage-aware preemption (Trinity's third pillar): a running slot can be
*evicted* between fused extend chunks — its full search state (query vector,
topM ids/dists, expanded flags, visited table, extend count) is pulled to a
host-side ``SlotCheckpoint`` and the slot freed — and later *restored*
bit-identically into any free slot (of this or another replica over the same
index). Because one extend step is pure per-slot state → state (PRNG is only
consumed at admission, and slots never interact), a resumed search emits the
same ids/dists and the same total extend count as an uninterrupted one —
asserted in tests/test_preemption.py. Engine API: ``preempt(request_ids)``
→ ``[(rid, SlotCheckpoint), ...]`` (one gather dispatch + one host sync),
``resume_batch([(rid, ckpt), ...])`` (one scatter dispatch, power-of-two
padded like ``admit_many``). The preemption *policy* — who gets evicted and
when — lives in core/scheduler.py; the pool (core/trinity_pool.py) wires the
two together between chunks.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.kernels import ops as kernel_ops
from repro.kernels import ref as kernel_ref
from repro.vector.cagra import INF, _hash_probe, _merge_topm


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EngineState:
    query_vecs: jnp.ndarray  # (R, d)
    top_ids: jnp.ndarray  # (R, M)
    top_dists: jnp.ndarray  # (R, M)
    expanded: jnp.ndarray  # (R, M) bool
    visited: jnp.ndarray  # (R, V) int32
    active: jnp.ndarray  # (R,) bool
    extends: jnp.ndarray  # (R,) int32
    budget: jnp.ndarray  # (R,) int32 — forced-completion extend budget, 0=off

    def tree_flatten(self):
        return ((self.query_vecs, self.top_ids, self.top_dists, self.expanded,
                 self.visited, self.active, self.extends, self.budget), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_engine_state(cfg, dtype=jnp.float32) -> EngineState:
    R, M, V = cfg.max_requests, cfg.top_m, cfg.visited_slots
    return EngineState(
        query_vecs=jnp.zeros((R, cfg.dim), dtype),
        top_ids=jnp.full((R, M), -1, jnp.int32),
        top_dists=jnp.full((R, M), INF),
        expanded=jnp.zeros((R, M), bool),
        visited=jnp.full((R, V), -1, jnp.int32),
        active=jnp.zeros((R,), bool),
        extends=jnp.zeros((R,), jnp.int32),
        budget=jnp.zeros((R,), jnp.int32),
    )


@dataclasses.dataclass(frozen=True)
class SlotParams:
    """Per-slot search parameters, derived from a request's retrieval
    class by the pool. ``entry_hi = 0`` means "the engine's corpus rows"
    (resolved host-side at admission)."""

    top_k: Optional[int] = None  # result truncation (None = cfg.top_k)
    budget: int = 0  # forced completion after this many extends (0 = off)
    entry_lo: int = 0  # entry-point sampling range [lo, hi)
    entry_hi: int = 0


DEFAULT_PARAMS = SlotParams()

# Entry-point keys are derived from the request id, NOT from a sequentially
# consumed stream: fold_in(engine key, rid & _RID_MASK). A request's search
# result is then a pure function of (qvec, rid), independent of admission
# order — preemption/re-admission reordering cannot perturb recall, and the
# on/off benchmark arms return bit-identical result sets.
_RID_MASK = 0x7FFFFFFF


# ---------------------------------------------------------------------------
# jitted slot admission
# ---------------------------------------------------------------------------


def _place_corpus(db, use_pallas: bool):
    """The engine's device copy of the corpus: (N, d) for the jnp path,
    the distance kernel's (N, 1, d_pad) layout for the Pallas path."""
    return kernel_ops.corpus_layout(db) if use_pallas else jnp.asarray(db)


def _corpus_rows(rows, dim: int):
    """Gathered corpus rows in either ``_place_corpus`` layout → (n, dim)
    float32 (drops the kernel layout's unit axis and zero lanes)."""
    return rows.reshape(rows.shape[0], -1)[:, :dim].astype(jnp.float32)


def _seed_request(db, qvec, entry_key, entry_lo, entry_hi, *, top_m: int,
                  visited_slots: int, num_entries: int, metric: str):
    """Seeding body of one request in ``admit_many``: random entry points
    in ``[entry_lo, entry_hi)`` (the slot's index segment) + their exact
    distances (metric-aware), padded to topM, entries inserted into a fresh
    visited row. The range bounds are traced scalars, so heterogeneous
    segments share one compile."""
    entries = jax.random.randint(entry_key, (num_entries,), entry_lo,
                                 entry_hi)
    x = _corpus_rows(db[entries], qvec.shape[-1])
    q = qvec[None].astype(jnp.float32)
    if metric == "l2":
        d = jnp.sum((x - q) ** 2, axis=-1)
    elif metric == "ip":
        d = -jnp.sum(x * q, axis=-1)
    else:
        raise ValueError(f"unknown metric: {metric!r}")
    pad = top_m - num_entries
    ids = jnp.concatenate([entries.astype(jnp.int32),
                           jnp.full((pad,), -1, jnp.int32)])
    dists = jnp.concatenate([d, jnp.full((pad,), INF)])
    visited_row = jnp.full((visited_slots,), -1, jnp.int32)
    visited_row, _ = _hash_probe(visited_row, entries.astype(jnp.int32))
    return ids, dists, visited_row


@functools.partial(jax.jit, static_argnames=("num_entries", "metric"),
                   donate_argnums=(0,))
def admit_many(state: EngineState, db, base_key, cols, qvecs,
               num_entries: int = 16, metric: str = "l2"):
    """Seat a whole scheduler batch in one dispatch: reset each slot, seed
    its topM with random entry points (ids + exact distances) from the
    slot's index segment, insert them into visited, arm the extend budget.

    cols (B, 5) int32 holds per request its slot, request id masked to 31
    bits, entry_lo, entry_hi and budget; qvecs (B, d). Each request's entry
    key is ``fold_in(base_key, rid)``, derived here: the same bits as the
    eager ``fold_in``, so a request's entry points are a pure function of
    (base key, rid), independent of batch position and order. Duplicate
    slots (the host pads batches by replicating row 0) scatter identical
    values and are safe.
    """
    M = state.top_ids.shape[1]
    V = state.visited.shape[1]
    slots, rids, los, his, budgets = (cols[:, i] for i in range(5))
    seed = functools.partial(_seed_request, top_m=M, visited_slots=V,
                             num_entries=num_entries, metric=metric)
    ids, dists, visited_rows = jax.vmap(
        lambda q, r, lo, hi: seed(db, q, jax.random.fold_in(base_key, r),
                                  lo, hi))(qvecs, rids, los, his)
    B = slots.shape[0]
    return EngineState(
        query_vecs=state.query_vecs.at[slots].set(qvecs),
        top_ids=state.top_ids.at[slots].set(ids),
        top_dists=state.top_dists.at[slots].set(dists),
        expanded=state.expanded.at[slots].set(jnp.zeros((B, M), bool)),
        visited=state.visited.at[slots].set(visited_rows),
        active=state.active.at[slots].set(True),
        extends=state.extends.at[slots].set(jnp.zeros((B,), jnp.int32)),
        budget=state.budget.at[slots].set(budgets),
    )


# ---------------------------------------------------------------------------
# jitted slot eviction / restore (stage-aware preemption)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SlotCheckpoint:
    """Host-side snapshot of one slot's full search state. Restoring it
    into any free slot resumes the search bit-identically (slot identity
    never enters the math; PRNG is only consumed at admission)."""

    query_vec: np.ndarray  # (d,)
    top_ids: np.ndarray  # (M,)
    top_dists: np.ndarray  # (M,)
    expanded: np.ndarray  # (M,) bool
    visited: np.ndarray  # (V,) int32
    extends: int
    budget: int = 0  # per-slot forced-completion budget (0 = off)
    top_k: Optional[int] = None  # per-slot result truncation


@functools.partial(jax.jit, donate_argnums=(0,))
def evict_slots(state: EngineState, slots):
    """Gather the full per-slot state rows for ``slots`` and deactivate
    them. slots (B,) int32, padded by replicating entry 0 (duplicate
    gathers read identical rows; duplicate deactivations are idempotent).
    Returns (new_state, rows) with rows ordered like ``SlotCheckpoint``
    fields."""
    rows = (state.query_vecs[slots], state.top_ids[slots],
            state.top_dists[slots], state.expanded[slots],
            state.visited[slots], state.extends[slots], state.budget[slots])
    new_state = EngineState(
        query_vecs=state.query_vecs,
        top_ids=state.top_ids,
        top_dists=state.top_dists,
        expanded=state.expanded,
        visited=state.visited,
        active=state.active.at[slots].set(False),
        extends=state.extends,
        budget=state.budget,
    )
    return new_state, rows


@jax.jit
def snapshot_slots(state: EngineState, slots):
    """Non-destructive ``evict_slots``: gather the full per-slot state rows
    for ``slots`` WITHOUT deactivating them (the searches keep running).
    The pool's checkpoint-rescue path snapshots in-flight slots host-side
    each fused chunk so a replica death can resume instead of restart.
    The state is not donated — it stays live on device."""
    return (state.query_vecs[slots], state.top_ids[slots],
            state.top_dists[slots], state.expanded[slots],
            state.visited[slots], state.extends[slots], state.budget[slots])


@functools.partial(jax.jit, donate_argnums=(0,))
def restore_slots(state: EngineState, slots, query_vecs, top_ids, top_dists,
                  expanded, visited, extends, budgets):
    """Scatter checkpointed rows back into ``slots`` and reactivate them —
    the exact inverse of ``evict_slots``. Duplicate (padding) slots scatter
    identical values and are safe."""
    return EngineState(
        query_vecs=state.query_vecs.at[slots].set(query_vecs),
        top_ids=state.top_ids.at[slots].set(top_ids),
        top_dists=state.top_dists.at[slots].set(top_dists),
        expanded=state.expanded.at[slots].set(expanded),
        visited=state.visited.at[slots].set(visited),
        active=state.active.at[slots].set(True),
        extends=state.extends.at[slots].set(extends),
        budget=state.budget.at[slots].set(budgets),
    )


# ---------------------------------------------------------------------------
# the jitted extend step (fixed shapes end to end)
# ---------------------------------------------------------------------------


def slot_buckets(cfg) -> Tuple[int, ...]:
    """The slot counts ``extend_multi`` may run over, ascending: each power
    of two below R whose task array, ``task_batch · Rb / R``, is a whole
    number of the distance kernel's task blocks, then R itself."""
    R, T, block = cfg.max_requests, cfg.task_batch, kernel_ops.TASK_BLOCK
    small = [1 << i for i in range(R.bit_length())
             if (1 << i) < R and T * (1 << i) % (R * block) == 0]
    return tuple(small) + (R,)


def _build_tasks(state: EngineState, graph, p: int):
    """Stages 1–3: parent selection, neighbour gather, visited filter,
    global task emission. Returns (task_ids, task_slot (R*p*D,), updated
    expanded/visited, parent_ok (R,p))."""
    R, M = state.top_ids.shape
    D = graph.shape[1]

    def per_slot(tid, td, exp, vis, active):
        rank = jnp.where(exp | (tid < 0), INF, td)
        # p smallest ranks via top_k on the negation: O(M·p) vs a full
        # O(M log M) argsort; ties break to the lower index in both.
        neg_best, parent_ix = jax.lax.top_k(-rank, p)
        ok = (-neg_best < INF) & active
        parents = jnp.where(ok, jnp.take(tid, parent_ix), -1)
        exp = exp.at[parent_ix].set(exp[parent_ix] | ok)
        nbrs = jnp.where(parents[:, None] >= 0,
                         graph[jnp.maximum(parents, 0)], -1).reshape(-1)
        vis, seen = _hash_probe(vis, nbrs)
        nbrs = jnp.where(seen, -1, nbrs)
        return nbrs, exp, vis, ok

    nbrs, expanded, visited, parent_ok = jax.vmap(per_slot)(
        state.top_ids, state.top_dists, state.expanded, state.visited,
        state.active)
    task_ids = nbrs.reshape(-1)  # (R*p*D,)
    task_slot = jnp.repeat(jnp.arange(R, dtype=jnp.int32), p * D)
    return task_ids, task_slot, expanded, visited, parent_ok


def _extend_impl(state: EngineState, db, graph, *, p: int, task_batch: int,
                 use_pallas: bool = False, metric: str = "l2",
                 distance_mode: str = "slot_gather"):
    """One engine iteration (traceable body shared by ``extend_step`` and
    the fused ``extend_multi`` scan).

    Returns (new_state, completed (R,) bool, tasks_emitted scalar)."""
    R, M = state.top_ids.shape
    D = graph.shape[1]
    with jax.named_scope("build_tasks"):
        task_ids, task_slot, expanded, visited, parent_ok = _build_tasks(
            state, graph, p)

    n_emit = task_ids.shape[0]
    assert n_emit <= task_batch, (n_emit, task_batch)
    pad = task_batch - n_emit
    with jax.named_scope("pad_tasks"):
        task_ids_p = jnp.concatenate([task_ids,
                                      jnp.full((pad,), -1, jnp.int32)])
        task_slot_p = jnp.concatenate([task_slot,
                                       jnp.zeros((pad,), jnp.int32)])

    # ---- stage 4: ONE fixed-shape distance operator ----------------------
    with jax.named_scope("distance"):
        if use_pallas:
            if distance_mode != "slot_gather":
                raise ValueError(f"distance_mode {distance_mode!r} has no "
                                 "Pallas kernel; it runs on the jnp path "
                                 "only (use_pallas=False)")
            dists = kernel_ops.distance_tasks(db, state.query_vecs,
                                              task_ids_p, task_slot_p,
                                              metric=metric)
        elif distance_mode == "matmul_onehot":
            dists = kernel_ref.distance_tasks_onehot_ref(
                db, state.query_vecs, task_ids_p, task_slot_p, metric=metric)
        elif distance_mode == "slot_gather":
            dists = kernel_ref.distance_tasks_ref(
                db, state.query_vecs, task_ids_p, task_slot_p, metric=metric)
        else:
            raise ValueError(f"unknown distance mode: {distance_mode!r}")
        dists = dists[:n_emit].reshape(R, p * D)
    cand_ids = task_ids.reshape(R, p * D)

    # ---- stage 5: scatter back + per-slot topM merge ---------------------
    with jax.named_scope("merge_topm"):
        top_ids, top_dists, expanded = jax.vmap(_merge_topm)(
            state.top_ids, state.top_dists, expanded, cand_ids, dists)

    # ---- stage 6: convergence = no parent was expandable, OR the slot's
    # extend budget is exhausted (forced completion: the budgeted extend
    # still runs and merges before the slot exits) ---------------------------
    with jax.named_scope("converge"):
        did_work = jnp.any(parent_ok, axis=1)
        extends = state.extends + jnp.where(state.active & did_work, 1, 0)
        over_budget = (state.budget > 0) & (extends >= state.budget)
        completed = state.active & (~did_work | over_budget)
        new_active = state.active & did_work & ~over_budget
        tasks_emitted = jnp.sum(task_ids >= 0)

    new_state = EngineState(state.query_vecs, top_ids, top_dists, expanded,
                            visited, new_active, extends, state.budget)
    return new_state, completed, tasks_emitted


@functools.partial(jax.jit, static_argnames=("p", "use_pallas", "task_batch",
                                             "metric", "distance_mode"),
                   donate_argnums=(0,))
def extend_step(state: EngineState, db, graph, *, p: int, task_batch: int,
                use_pallas: bool = False, metric: str = "l2",
                distance_mode: str = "slot_gather"):
    """One continuous-batching engine iteration.

    Returns (new_state, completed (R,) bool, tasks_emitted scalar)."""
    return _extend_impl(state, db, graph, p=p, task_batch=task_batch,
                        use_pallas=use_pallas, metric=metric,
                        distance_mode=distance_mode)


@functools.partial(jax.jit, static_argnames=("num_steps", "p", "use_pallas",
                                             "task_batch", "metric",
                                             "distance_mode", "num_slots"),
                   donate_argnums=(0,))
def extend_multi(state: EngineState, db, graph, *, num_steps: int, p: int,
                 task_batch: int, use_pallas: bool = False,
                 metric: str = "l2", distance_mode: str = "slot_gather",
                 num_slots: Optional[int] = None):
    """K fused engine iterations in ONE dispatch (``lax.scan`` over
    ``_extend_impl``). Requests that complete at sub-step i stay inactive
    (and their slot state untouched) for the remaining sub-steps, so the
    result is bit-identical to K sequential ``extend_step`` calls.

    ``num_slots`` (a bucket of ``slot_buckets``; default R) runs the scan
    over that many slots, active first and then inactive ones, each slot
    once, with ``task_batch · num_slots / R`` tasks; the caller keeps every
    active slot inside it. Slots left out are untouched.

    Returns (new_state, completed (K, R) bool, tasks_emitted (K,) int32) —
    stacked device arrays; the host syncs once per K steps."""
    R = state.active.shape[0]
    rb = R if num_slots is None else num_slots
    assert 0 < rb <= R and task_batch * rb % R == 0, (rb, R, task_batch)

    def body(st, _):
        st, completed, tasks = _extend_impl(
            st, db, graph, p=p, task_batch=task_batch * rb // R,
            use_pallas=use_pallas, metric=metric,
            distance_mode=distance_mode)
        return st, (completed, tasks)

    if rb == R:  # every slot: the state as it lies, no gather
        state, (completed_k, tasks_k) = jax.lax.scan(
            body, state, None, length=num_steps)
        return state, completed_k, tasks_k
    idx = jnp.argsort(~state.active, stable=True)[:rb]
    sub = jax.tree_util.tree_map(lambda x: x[idx], state)
    sub, (completed_k, tasks_k) = jax.lax.scan(
        body, sub, None, length=num_steps)
    state = jax.tree_util.tree_map(lambda x, y: x.at[idx].set(y), state, sub)
    completed_k = jnp.zeros((num_steps, R), bool).at[:, idx].set(completed_k)
    return state, completed_k, tasks_k


# ---------------------------------------------------------------------------
# host-side engine wrapper (slot freelist, admission, completion collection)
# ---------------------------------------------------------------------------


class ContinuousBatchingEngine:
    """Host wrapper owning device state + the slot freelist.

    ``use_pallas=None`` auto-selects: Pallas kernel on TPU, jnp oracle on
    CPU (identical results — asserted in tests/test_continuous_batching).

    Hot-path dispatch discipline: ``num_active`` is tracked host-side (the
    freelist/slot-map already knows it — no device readback), admissions go
    through one vmapped ``admit_many`` dispatch per scheduler batch
    (``admit_batch``), and ``step_multi`` fuses K extend steps into one
    device dispatch over the smallest slot bucket that holds ``num_active``,
    with a single host sync for the stacked completion masks + task counts.
    ``dispatched_slots`` is the bucket of the last dispatch.
    """

    def __init__(self, cfg, db: np.ndarray, graph: np.ndarray,
                 use_pallas: Optional[bool] = None, seed: int = 0,
                 corpus_rows: Optional[int] = None):
        self.cfg = cfg
        self.use_pallas = (jax.default_backend() == "tpu"
                           if use_pallas is None else use_pallas)
        self.db = _place_corpus(db, self.use_pallas)
        self.graph = jnp.asarray(graph)
        # rows [0, corpus_n) are the frozen corpus segment; rows beyond are
        # a growable segment (online inserts) that default admissions must
        # not sample entry points from
        self.corpus_n = db.shape[0] if corpus_rows is None else corpus_rows
        self.state = init_engine_state(cfg)
        self.free_slots = list(range(cfg.max_requests))[::-1]
        self.slot_request = {}  # slot -> request id
        self.slot_topk = {}  # slot -> per-slot top-k truncation (optional)
        self.distance_mode = cfg.distance_mode
        self.extend_chunk = max(1, cfg.extend_chunk)
        self._key = jax.random.PRNGKey(seed)
        self.total_tasks = 0  # distance tasks emitted, summed over steps
        self.slot_buckets = slot_buckets(cfg)
        self.dispatched_slots = cfg.max_requests

    @property
    def num_active(self) -> int:
        # the host already knows which slots are in flight — no device sync
        return len(self.slot_request)

    @property
    def num_free(self) -> int:
        return len(self.free_slots)

    def _resolve_params(self, params: Optional[SlotParams]):
        """(entry_lo, entry_hi, budget, top_k) with segment defaulting to
        the frozen corpus rows."""
        p = params or DEFAULT_PARAMS
        hi = p.entry_hi if p.entry_hi > 0 else self.corpus_n
        return p.entry_lo, hi, p.budget, p.top_k

    def admit(self, request_id, qvec, params: Optional[SlotParams] = None) -> int:
        return self.admit_batch([(request_id, qvec, params)])[0]

    def admit_batch(self, requests) -> List[int]:
        """Admit ``[(request_id, qvec), ...]`` — optionally
        ``(request_id, qvec, SlotParams)`` — in ONE jitted dispatch.

        The host builds two NumPy buffers, an int32 table of (slot, masked
        request id, entry_lo, entry_hi, budget) rows and the float32 query
        block, and hands them to ``admit_many``, which derives the entry
        keys itself: no eager JAX op runs before it. The batch is padded
        to a power-of-two bucket (by replicating row 0 — duplicate scatters
        write identical values) so only O(log max_requests) distinct shapes
        ever compile. Results are bit-identical to sequential ``admit``
        calls in any order."""
        if not requests:
            return []
        requests = [r if len(r) == 3 else (r[0], r[1], None)
                    for r in requests]
        B = len(requests)
        assert B <= len(self.free_slots), (B, len(self.free_slots))
        slots = [self.free_slots.pop() for _ in range(B)]
        resolved = [self._resolve_params(p) for _, _, p in requests]
        b_pad = 1 << (B - 1).bit_length()
        cols = np.empty((b_pad, 5), np.int32)
        qvecs = np.empty((b_pad, self.cfg.dim), np.float32)
        for i, (slot, (rid, q, _), (lo, hi, budget, _)) in enumerate(
                zip(slots, requests, resolved)):
            cols[i] = (slot, int(rid) & _RID_MASK, lo, hi, budget)
            qvecs[i] = q
        cols[B:] = cols[0]
        qvecs[B:] = qvecs[0]
        self.state = admit_many(self.state, self.db, self._key, cols, qvecs,
                                num_entries=min(16, self.cfg.top_m // 2),
                                metric=self.cfg.metric)
        for slot, (rid, _, _), (_, _, _, top_k) in zip(slots, requests,
                                                       resolved):
            self.slot_request[slot] = rid
            if top_k is not None:
                self.slot_topk[slot] = top_k
        return slots

    def set_index(self, db, graph, corpus_rows: Optional[int] = None):
        """Swap in grown index arrays (online inserts). In-flight searches
        simply see the new rows on their next extend — semantically a
        regular ANN index update. A capacity growth (shape change) costs
        one fresh jit specialisation, bounded O(log capacity) times. On the
        Pallas path each swap also re-lays-out the corpus (one copy)."""
        self.db = _place_corpus(db, self.use_pallas)
        self.graph = jnp.asarray(graph)
        if corpus_rows is not None:
            self.corpus_n = corpus_rows

    def preempt(self, request_ids) -> List[Tuple[int, SlotCheckpoint]]:
        """Evict the slots running ``request_ids``: one jitted gather
        dispatch + one host sync pulls their full search state into
        host-side ``SlotCheckpoint``s and frees the slots. Restoring a
        checkpoint (here or on another replica over the same db/graph)
        resumes the search bit-identically."""
        if not request_ids:
            return []
        slot_of = {rid: slot for slot, rid in self.slot_request.items()}
        slots = [slot_of[rid] for rid in request_ids]
        B = len(slots)
        pad = (1 << (B - 1).bit_length()) - B
        slots_p = jnp.asarray(np.asarray(slots + slots[:1] * pad, np.int32))
        self.state, rows = evict_slots(self.state, slots_p)
        rows = jax.device_get(rows)  # the one host sync per preemption
        qv, ids, dists, exp, vis, ext, bud = (np.asarray(r) for r in rows)
        out = []
        for i, (rid, slot) in enumerate(zip(request_ids, slots)):
            out.append((rid, SlotCheckpoint(
                query_vec=qv[i].copy(), top_ids=ids[i].copy(),
                top_dists=dists[i].copy(), expanded=exp[i].copy(),
                visited=vis[i].copy(), extends=int(ext[i]),
                budget=int(bud[i]), top_k=self.slot_topk.pop(slot, None))))
            del self.slot_request[slot]
            self.free_slots.append(slot)
        return out

    def snapshot(self, request_ids) -> List[Tuple[int, SlotCheckpoint]]:
        """Host-side checkpoints of the slots running ``request_ids``
        WITHOUT evicting them (the searches keep running): one jitted
        gather dispatch + one host sync, same cost as ``preempt`` minus
        the slot bookkeeping. Because a fused chunk is the only thing that
        advances slot state, a snapshot taken between chunks IS the exact
        state at any failure landing before the next chunk — restoring it
        on another replica over the same db/graph resumes the search
        bit-identically (checkpoint-rescue on replica death)."""
        if not request_ids:
            return []
        slot_of = {rid: slot for slot, rid in self.slot_request.items()}
        slots = [slot_of[rid] for rid in request_ids]
        B = len(slots)
        pad = (1 << (B - 1).bit_length()) - B
        slots_p = jnp.asarray(np.asarray(slots + slots[:1] * pad, np.int32))
        rows = jax.device_get(snapshot_slots(self.state, slots_p))
        qv, ids, dists, exp, vis, ext, bud = (np.asarray(r) for r in rows)
        out = []
        for i, (rid, slot) in enumerate(zip(request_ids, slots)):
            out.append((rid, SlotCheckpoint(
                query_vec=qv[i].copy(), top_ids=ids[i].copy(),
                top_dists=dists[i].copy(), expanded=exp[i].copy(),
                visited=vis[i].copy(), extends=int(ext[i]),
                budget=int(bud[i]), top_k=self.slot_topk.get(slot, None))))
        return out

    def resume_batch(self, items) -> List[int]:
        """Re-seat ``[(request_id, SlotCheckpoint), ...]`` into free slots
        in ONE jitted scatter dispatch (power-of-two padded like
        ``admit_batch``). Returns the slots used."""
        if not items:
            return []
        B = len(items)
        assert B <= len(self.free_slots), (B, len(self.free_slots))
        slots = [self.free_slots.pop() for _ in range(B)]
        pad = (1 << (B - 1).bit_length()) - B
        slots_p = jnp.asarray(np.asarray(slots + slots[:1] * pad, np.int32))
        stack = lambda f: np.stack([f(c) for _, c in items]
                                   + [f(items[0][1])] * pad)
        self.state = restore_slots(
            self.state, slots_p,
            jnp.asarray(stack(lambda c: np.asarray(c.query_vec, np.float32))),
            jnp.asarray(stack(lambda c: np.asarray(c.top_ids, np.int32))),
            jnp.asarray(stack(lambda c: np.asarray(c.top_dists, np.float32))),
            jnp.asarray(stack(lambda c: np.asarray(c.expanded, bool))),
            jnp.asarray(stack(lambda c: np.asarray(c.visited, np.int32))),
            jnp.asarray(stack(lambda c: np.int32(c.extends))),
            jnp.asarray(stack(lambda c: np.int32(getattr(c, "budget", 0)))),
        )
        for slot, (rid, ckpt) in zip(slots, items):
            self.slot_request[slot] = rid
            top_k = getattr(ckpt, "top_k", None)
            if top_k is not None:
                self.slot_topk[slot] = top_k
        return slots

    def step_multi(self, num_steps: Optional[int] = None):
        """K fused extends over all active slots — one dispatch, one sync,
        over the smallest slot bucket that holds them.

        Returns (completions, tasks_per_step (K,) np.int32); completions
        are (request_id, topk_ids, topk_dists, extends_used, substep) with
        ``substep`` ∈ [0, K) the extend at which the request converged (for
        exact completion-time attribution in the pool)."""
        k = self.extend_chunk if num_steps is None else num_steps
        rb = next(b for b in self.slot_buckets if b >= self.num_active)
        self.dispatched_slots = rb
        with tracing.span("dispatch", active=self.num_active, slots=rb):
            self.state, completed_k, tasks_k = extend_multi(
                self.state, self.db, self.graph, num_steps=k,
                p=self.cfg.parents_per_step, task_batch=self.cfg.task_batch,
                use_pallas=self.use_pallas, metric=self.cfg.metric,
                distance_mode=self.distance_mode, num_slots=rb)
        # the ONE host-device sync for this dispatch
        with tracing.span("sync"):
            completed_k, tasks_k = jax.device_get((completed_k, tasks_k))
        with tracing.span("collect", done=int(completed_k.sum())):
            self.total_tasks += int(tasks_k.sum())
            out = []
            if completed_k.any():
                top_ids = np.asarray(self.state.top_ids)
                top_dists = np.asarray(self.state.top_dists)
                extends = np.asarray(self.state.extends)
                for i in range(k):
                    for slot in np.nonzero(completed_k[i])[0]:
                        rid = self.slot_request.pop(int(slot))
                        # per-slot top-k truncation (retrieval classes)
                        kk = self.slot_topk.pop(int(slot), self.cfg.top_k)
                        out.append((rid, top_ids[slot, :kk].copy(),
                                    top_dists[slot, :kk].copy(),
                                    int(extends[slot]), i))
                        self.free_slots.append(int(slot))
        return out, tasks_k

    def step(self) -> Tuple[List[Tuple[int, np.ndarray, np.ndarray, int]], int]:
        """One extend over all active slots.

        Returns (completions, tasks_emitted); completions are
        (request_id, topk_ids, topk_dists, extends_used)."""
        comps, tasks_k = self.step_multi(1)
        return [(rid, ids, dists, ext) for rid, ids, dists, ext, _ in comps], \
            int(tasks_k[0])

    def run_to_completion(self, max_steps: int = 256):
        """Drain all active requests (used by tests/benchmarks).

        Chunk sizes are restricted to {1, extend_chunk} so only two scan
        shapes ever compile (an arbitrary tail chunk would trigger a fresh
        XLA compile of the whole K-step program)."""
        done = []
        steps = 0
        while steps < max_steps:
            if self.num_active == 0:
                break
            chunk = self.extend_chunk \
                if max_steps - steps >= self.extend_chunk else 1
            c, _ = self.step_multi(chunk)
            done.extend((rid, ids, dists, ext) for rid, ids, dists, ext, _ in c)
            steps += chunk
        return done


# ---------------------------------------------------------------------------
# megabatched cross-shard dispatch: grouped (lane-stacked) engine state
# ---------------------------------------------------------------------------
#
# Since PR 4 every shard's frozen segment is padded to one common shape, so
# all shard engines share ONE compiled program — which means their per-lane
# EngineState pytrees stack into a (G, R, …) layout and a single vmapped
# ``_extend_impl`` advances every lane in ONE device dispatch. The grouped
# jitted functions below mirror their per-engine counterparts exactly;
# per-lane math is bit-identical to serial stepping (vmap adds a batch
# dimension, it does not reassociate the per-lane reductions — asserted in
# tests/test_dispatch_pipeline.py), and lanes outside the stepping cohort
# are frozen bit-wise by a ``jnp.where`` over the group-active mask.


def _seed_request_g(dbs, g, qvec, entry_key, entry_lo, entry_hi, *,
                    top_m: int, visited_slots: int, num_entries: int,
                    metric: str):
    """``_seed_request`` against lane ``g`` of the stacked (G, N, d) index.
    ``dbs[g, entries]`` gathers only the sampled rows — indexing the lane
    first would materialise a (B, N, d) copy under vmap."""
    entries = jax.random.randint(entry_key, (num_entries,), entry_lo,
                                 entry_hi)
    x = _corpus_rows(dbs[g, entries], qvec.shape[-1])
    q = qvec[None].astype(jnp.float32)
    if metric == "l2":
        d = jnp.sum((x - q) ** 2, axis=-1)
    elif metric == "ip":
        d = -jnp.sum(x * q, axis=-1)
    else:
        raise ValueError(f"unknown metric: {metric!r}")
    pad = top_m - num_entries
    ids = jnp.concatenate([entries.astype(jnp.int32),
                           jnp.full((pad,), -1, jnp.int32)])
    dists = jnp.concatenate([d, jnp.full((pad,), INF)])
    visited_row = jnp.full((visited_slots,), -1, jnp.int32)
    visited_row, _ = _hash_probe(visited_row, entries.astype(jnp.int32))
    return ids, dists, visited_row


@functools.partial(jax.jit, static_argnames=("num_entries", "metric"),
                   donate_argnums=(0,))
def admit_many_group(state: EngineState, dbs, g_idx, slots, qvecs,
                     entry_keys, entry_los, entry_his, budgets,
                     num_entries: int = 16, metric: str = "l2"):
    """``admit_many`` over stacked lane state: one vmapped seeding + one
    scatter at (lane, slot) pairs covers every cohort member's flush.
    Batches are power-of-two padded by replicating entry 0 (duplicate
    scatters write identical values). Seeded values are bit-identical to
    the per-engine ``admit_many`` — both paths run ``_seed_request``'s ops
    on the same rows."""
    M = state.top_ids.shape[2]
    V = state.visited.shape[2]
    seed = functools.partial(_seed_request_g, top_m=M, visited_slots=V,
                             num_entries=num_entries, metric=metric)
    ids, dists, visited_rows = jax.vmap(
        lambda g, q, k, lo, hi: seed(dbs, g, q, k, lo, hi))(
        g_idx, qvecs, entry_keys, entry_los, entry_his)
    B, Mw = ids.shape
    return EngineState(
        query_vecs=state.query_vecs.at[g_idx, slots].set(qvecs),
        top_ids=state.top_ids.at[g_idx, slots].set(ids),
        top_dists=state.top_dists.at[g_idx, slots].set(dists),
        expanded=state.expanded.at[g_idx, slots].set(
            jnp.zeros((B, Mw), bool)),
        visited=state.visited.at[g_idx, slots].set(visited_rows),
        active=state.active.at[g_idx, slots].set(True),
        extends=state.extends.at[g_idx, slots].set(
            jnp.zeros((B,), jnp.int32)),
        budget=state.budget.at[g_idx, slots].set(budgets),
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def evict_slots_group(state: EngineState, g_idx, slots):
    """``evict_slots`` at (lane, slot) pairs: gather the full rows and
    deactivate them. Row order matches ``SlotCheckpoint`` fields."""
    rows = (state.query_vecs[g_idx, slots], state.top_ids[g_idx, slots],
            state.top_dists[g_idx, slots], state.expanded[g_idx, slots],
            state.visited[g_idx, slots], state.extends[g_idx, slots],
            state.budget[g_idx, slots])
    new_state = dataclasses.replace(
        state, active=state.active.at[g_idx, slots].set(False))
    return new_state, rows


@jax.jit
def snapshot_slots_group(state: EngineState, g_idx, slots):
    """Non-destructive grouped gather of full slot rows (checkpoint
    rescue: ONE dispatch + sync covers every cohort member's in-flight
    slots instead of one per replica)."""
    return (state.query_vecs[g_idx, slots], state.top_ids[g_idx, slots],
            state.top_dists[g_idx, slots], state.expanded[g_idx, slots],
            state.visited[g_idx, slots], state.extends[g_idx, slots],
            state.budget[g_idx, slots])


@functools.partial(jax.jit, donate_argnums=(0,))
def restore_slots_group(state: EngineState, g_idx, slots, query_vecs,
                        top_ids, top_dists, expanded, visited, extends,
                        budgets):
    """Grouped ``restore_slots``: scatter checkpointed rows back into
    (lane, slot) pairs and reactivate them."""
    return EngineState(
        query_vecs=state.query_vecs.at[g_idx, slots].set(query_vecs),
        top_ids=state.top_ids.at[g_idx, slots].set(top_ids),
        top_dists=state.top_dists.at[g_idx, slots].set(top_dists),
        expanded=state.expanded.at[g_idx, slots].set(expanded),
        visited=state.visited.at[g_idx, slots].set(visited),
        active=state.active.at[g_idx, slots].set(True),
        extends=state.extends.at[g_idx, slots].set(extends),
        budget=state.budget.at[g_idx, slots].set(budgets),
    )


@jax.jit
def collect_slots_group(state: EngineState, g_idx, slots):
    """Completion collection: gather ONLY the result columns (top ids,
    top dists, extend counts) of finishing (lane, slot) pairs — one
    transfer per collected chunk instead of three full-state ``np.asarray``
    pulls per completing engine (the PR-8 satellite)."""
    return (state.top_ids[g_idx, slots], state.top_dists[g_idx, slots],
            state.extends[g_idx, slots])


@jax.jit
def collect_extends_group(state: EngineState, g_idx, slots):
    """Extend-count-only gather: with the on-device merge, a search
    child's ids/dists stay device handles — the host needs ONLY its
    extends count (fan-out accounting), a (B,) transfer."""
    return state.extends[g_idx, slots]


@functools.partial(jax.jit, static_argnames=("num_steps", "p", "use_pallas",
                                             "task_batch", "metric",
                                             "distance_mode"),
                   donate_argnums=(0,))
def extend_multi_group(state: EngineState, dbs, graphs, group_active, *,
                       num_steps: int, p: int, task_batch: int,
                       use_pallas: bool = False, metric: str = "l2",
                       distance_mode: str = "slot_gather"):
    """K fused extend steps over EVERY lane in one dispatch: a
    ``lax.scan`` whose body vmaps ``_extend_impl`` across the stacked
    (G, R, …) state with per-lane (N, d) index arrays. Lanes outside
    ``group_active`` still compute (the batch shape is fixed) but their
    state is frozen bit-wise by the trailing ``where`` — masked-lane
    wasted compute buys one dispatch + one sync for the whole cohort.

    Returns (state, completed (K, G, R) bool, tasks (K, G) int32)."""

    def one(st, db, graph):
        return _extend_impl(st, db, graph, p=p, task_batch=task_batch,
                            use_pallas=use_pallas, metric=metric,
                            distance_mode=distance_mode)

    def body(st, _):
        new, completed, tasks = jax.vmap(one)(st, dbs, graphs)
        frozen = jax.tree_util.tree_map(
            lambda n, o: jnp.where(
                group_active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
            new, st)
        completed = completed & group_active[:, None]
        tasks = jnp.where(group_active, tasks, 0)
        return frozen, (completed, tasks)

    state, (completed_k, tasks_k) = jax.lax.scan(
        body, state, None, length=num_steps)
    return state, completed_k, tasks_k


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _set_lane_index(dbs, graphs, g, db, graph):
    """Copy one lane's grown index arrays into the stacked (G, N, d) /
    (G, N, D) buffers. Unlike the per-engine ``set_index`` (a pointer
    swap), the grouped layout pays a lane-sized copy per insert broadcast
    — the price of keeping every lane inside one compiled program."""
    n = db.shape[0]
    return dbs.at[g, :n].set(db), graphs.at[g, :n].set(graph)


@functools.partial(jax.jit, donate_argnums=(0,))
def _deactivate_lane(state: EngineState, g):
    """Free a whole lane (member removal): deactivating every slot is
    enough — admission fully resets per-slot state on lane reuse, and
    inactive slots never touch the math (same as freed slots in the
    per-engine path)."""
    return dataclasses.replace(state, active=state.active.at[g].set(False))


def _pow2_pad(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


class GroupEngine:
    """Owner of the stacked per-lane device state for megabatched
    dispatch: lane-stacked ``EngineState`` (G, R, …) plus stacked index
    arrays (G, N, d) / (G, N, D). Lanes have a free-list lifecycle —
    removing a member just deactivates its lane, adding one reuses a free
    lane (admission resets slot state) — and capacity doubles O(log)
    times along both the lane axis and the row axis (online inserts
    growing a shard past the common row budget)."""

    def __init__(self, cfg, use_pallas: Optional[bool] = None):
        self.cfg = cfg
        self.use_pallas = (jax.default_backend() == "tpu"
                           if use_pallas is None else use_pallas)
        # per-row shape of the stacked corpus (see ``_place_corpus``)
        self._row_shape = tuple(_place_corpus(
            np.zeros((1, cfg.dim), np.float32), self.use_pallas).shape[1:])
        self.state: Optional[EngineState] = None
        self.dbs = None
        self.graphs = None
        self.g_cap = 0
        self.n_max = 0
        self._free_lanes: List[int] = []
        self.members: dict = {}  # lane -> GroupMember

    # ------------------------------------------------------ lane lifecycle
    def _grow_lanes(self, want: int):
        new_cap = max(4, self.g_cap)
        while new_cap < want:
            new_cap *= 2
        add = new_cap - self.g_cap
        if add <= 0:
            return
        init = init_engine_state(self.cfg)
        fresh = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (add,) + x.shape), init)
        if self.state is None:
            self.state = jax.tree_util.tree_map(jnp.array, fresh)
            self.dbs = jnp.zeros((new_cap, max(self.n_max, 1))
                                 + self._row_shape, jnp.float32)
            self.graphs = jnp.full((new_cap, max(self.n_max, 1),
                                    self.cfg.graph_degree), -1, jnp.int32)
            self.n_max = max(self.n_max, 1)
        else:
            self.state = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b], axis=0),
                self.state, fresh)
            self.dbs = jnp.concatenate(
                [self.dbs, jnp.zeros((add,) + self.dbs.shape[1:],
                                     self.dbs.dtype)], axis=0)
            self.graphs = jnp.concatenate(
                [self.graphs, jnp.full((add,) + self.graphs.shape[1:], -1,
                                       jnp.int32)], axis=0)
        self._free_lanes = list(range(new_cap - 1, self.g_cap - 1, -1)) \
            + self._free_lanes
        self.g_cap = new_cap

    def _ensure_rows(self, n: int):
        if n <= self.n_max:
            return
        new_n = max(self.n_max, 1)
        while new_n < n:
            new_n *= 2
        pad = new_n - self.n_max
        self.dbs = jnp.concatenate(
            [self.dbs, jnp.zeros((self.g_cap, pad) + self._row_shape,
                                 jnp.float32)], axis=1)
        self.graphs = jnp.concatenate(
            [self.graphs, jnp.full((self.g_cap, pad,
                                    self.cfg.graph_degree), -1, jnp.int32)],
            axis=1)
        self.n_max = new_n

    def add_member(self, index, seed: int) -> "GroupMember":
        if not self._free_lanes:
            self._grow_lanes(self.g_cap + 1)
        lane = self._free_lanes.pop()
        self.write_lane_index(lane, index.db, index.graph)
        member = GroupMember(self, lane, index, seed)
        self.members[lane] = member
        return member

    def free_lane(self, lane: int):
        self.members.pop(lane, None)
        self.state = _deactivate_lane(self.state, jnp.int32(lane))
        self._free_lanes.append(lane)

    def write_lane_index(self, lane: int, db, graph):
        self._ensure_rows(db.shape[0])
        self.dbs, self.graphs = _set_lane_index(
            self.dbs, self.graphs, jnp.int32(lane),
            _place_corpus(db, self.use_pallas), jnp.asarray(graph))

    # --------------------------------------------------------- device ops
    def _pad_pairs(self, entries):
        """(lane, slot) pairs → power-of-two padded device index arrays
        (padding replicates entry 0: duplicate gathers/scatters are
        safe)."""
        B = len(entries)
        padded = list(entries) + [entries[0]] * (_pow2_pad(B) - B)
        g_idx = jnp.asarray(np.asarray([g for g, _ in padded], np.int32))
        slots = jnp.asarray(np.asarray([s for _, s in padded], np.int32))
        return g_idx, slots

    def dispatch_admits(self, staged: List[dict]):
        """ONE ``admit_many_group`` dispatch covering every staged member
        flush (see ``GroupMember.stage_admit_batch``)."""
        staged = [s for s in staged if len(s["slots"])]
        if not staged:
            return
        entries = [(s["g"], slot) for s in staged for slot in s["slots"]]
        g_idx, slots = self._pad_pairs(entries)
        B = len(entries)
        pad = _pow2_pad(B) - B
        cat = lambda key: np.concatenate([s[key] for s in staged])
        qvecs = cat("qvecs")
        keys = [k for s in staged for k in s["keys"]]
        qvecs_p = np.concatenate([qvecs, qvecs[:1].repeat(pad, 0)]) \
            if pad else qvecs
        keys_p = jnp.stack(keys + keys[:1] * pad)
        pick = lambda key: jnp.asarray(np.concatenate(
            [cat(key), cat(key)[:1].repeat(pad, 0)]) if pad else cat(key))
        cfgv = self.cfg
        self.state = admit_many_group(
            self.state, self.dbs, g_idx, slots, jnp.asarray(qvecs_p),
            keys_p, pick("los"), pick("his"), pick("buds"),
            num_entries=min(16, cfgv.top_m // 2), metric=cfgv.metric)

    def dispatch_restores(self, staged: List[dict]):
        """ONE ``restore_slots_group`` dispatch for every staged member
        resume batch (see ``GroupMember.stage_resume_batch``)."""
        staged = [s for s in staged if len(s["slots"])]
        if not staged:
            return
        entries = [(s["g"], slot) for s in staged for slot in s["slots"]]
        g_idx, slots = self._pad_pairs(entries)
        B = len(entries)
        pad = _pow2_pad(B) - B
        def cat(key):
            x = np.concatenate([s[key] for s in staged])
            return jnp.asarray(np.concatenate([x, x[:1].repeat(pad, 0)])
                               if pad else x)
        self.state = restore_slots_group(
            self.state, g_idx, slots, cat("qv"), cat("ids"), cat("dists"),
            cat("exp"), cat("vis"), cat("ext"), cat("bud"))

    def step_lanes(self, lanes: List[int], num_steps: int):
        """K fused extend steps for the cohort ``lanes`` — ONE dispatch,
        one mask sync. Returns host (completed (K, G, R), tasks (K, G));
        lanes outside the cohort are frozen bit-wise."""
        pending = self.step_lanes_async(lanes, num_steps)
        with tracing.span("sync"):
            return jax.device_get(pending)

    def step_lanes_async(self, lanes: List[int], num_steps: int):
        """Double-buffered variant: dispatch the cohort chunk and return
        the UN-synced device arrays — the caller overlaps next-round host
        scheduling before blocking on them (``jax.device_get``)."""
        mask = np.zeros((self.g_cap,), bool)
        mask[lanes] = True
        cfgv = self.cfg
        with tracing.span("dispatch", lanes=len(lanes)):
            self.state, completed_k, tasks_k = extend_multi_group(
                self.state, self.dbs, self.graphs, jnp.asarray(mask),
                num_steps=num_steps, p=cfgv.parents_per_step,
                task_batch=cfgv.task_batch, use_pallas=self.use_pallas,
                metric=cfgv.metric, distance_mode=cfgv.distance_mode)
        return completed_k, tasks_k

    def collect_rows(self, entries):
        """Gather (top_ids (B, M), top_dists (B, M), extends (B,)) for
        finishing (lane, slot) pairs — one dispatch + one sync for ALL
        completions of a chunk."""
        if not entries:
            return (np.zeros((0, self.cfg.top_m), np.int32),
                    np.zeros((0, self.cfg.top_m), np.float32),
                    np.zeros((0,), np.int32))
        g_idx, slots = self._pad_pairs(entries)
        ids, dists, ext = jax.device_get(
            collect_slots_group(self.state, g_idx, slots))
        B = len(entries)
        return (np.asarray(ids)[:B], np.asarray(dists)[:B],
                np.asarray(ext)[:B])

    def gather_checkpoint_rows(self, entries):
        """Full-row snapshot gather for (lane, slot) pairs (grouped
        checkpoint rescue) — returns host arrays ordered like
        ``SlotCheckpoint`` fields, one sync for the whole cohort."""
        g_idx, slots = self._pad_pairs(entries)
        rows = jax.device_get(snapshot_slots_group(self.state, g_idx,
                                                   slots))
        B = len(entries)
        return tuple(np.asarray(r)[:B] for r in rows)


class GroupMember(ContinuousBatchingEngine):
    """Engine facade over one lane of a :class:`GroupEngine`: the exact
    ``ContinuousBatchingEngine`` host bookkeeping (freelist, slot→rid
    maps, per-request PRNG keys, metrics) with every device op routed
    through the shared stacked state. Pool code (cancel, hedging, kill
    rescue, replica moves) works unchanged against this API."""

    def __init__(self, group: GroupEngine, lane: int, index, seed: int):
        # deliberately NOT calling super().__init__: the lane owns no
        # private device arrays — state and index live in the group stacks
        self.group = group
        self.lane = lane
        self.cfg = group.cfg
        self.corpus_n = index.corpus_n
        self.free_slots = list(range(group.cfg.max_requests))[::-1]
        self.slot_request = {}
        self.slot_topk = {}
        self.use_pallas = group.use_pallas
        self.distance_mode = group.cfg.distance_mode
        self.extend_chunk = max(1, group.cfg.extend_chunk)
        self._key = jax.random.PRNGKey(seed)
        self.total_tasks = 0

    # ------------------------------------------------------- admission
    def _entry_key(self, request_id):
        return jax.random.fold_in(self._key, int(request_id) & _RID_MASK)

    def stage_admit_batch(self, requests) -> dict:
        """Host half of ``admit_batch``: pop slots, fold per-request PRNG
        keys, resolve per-slot params — returns the staged device args
        WITHOUT dispatching, so the pool can fold every cohort member's
        flush into one ``admit_many_group`` call."""
        requests = [r if len(r) == 3 else (r[0], r[1], None)
                    for r in requests]
        B = len(requests)
        assert B <= len(self.free_slots), (B, len(self.free_slots))
        slots = [self.free_slots.pop() for _ in range(B)]
        subs = [self._entry_key(rid) for rid, _, _ in requests]
        resolved = [self._resolve_params(p) for _, _, p in requests]
        for slot, (rid, _, _), (_, _, _, top_k) in zip(slots, requests,
                                                       resolved):
            self.slot_request[slot] = rid
            if top_k is not None:
                self.slot_topk[slot] = top_k
        pcols = np.asarray([r[:3] for r in resolved], np.int32) \
            if resolved else np.zeros((0, 3), np.int32)
        return {
            "g": self.lane,
            "slots": slots,
            "qvecs": (np.stack([np.asarray(q, np.float32)
                                for _, q, _ in requests]) if requests
                      else np.zeros((0, self.cfg.dim), np.float32)),
            "keys": subs,
            "los": pcols[:, 0], "his": pcols[:, 1], "buds": pcols[:, 2],
        }

    def admit_batch(self, requests) -> List[int]:
        if not requests:
            return []
        staged = self.stage_admit_batch(requests)
        self.group.dispatch_admits([staged])
        return staged["slots"]

    def admit(self, request_id, qvec,
              params: Optional[SlotParams] = None) -> int:
        return self.admit_batch([(request_id, qvec, params)])[0]

    def stage_resume_batch(self, items) -> dict:
        """Host half of ``resume_batch`` (checkpointed re-seating): pop
        slots + stack checkpoint rows, dispatch deferred to the group."""
        B = len(items)
        assert B <= len(self.free_slots), (B, len(self.free_slots))
        slots = [self.free_slots.pop() for _ in range(B)]
        for slot, (rid, ckpt) in zip(slots, items):
            self.slot_request[slot] = rid
            top_k = getattr(ckpt, "top_k", None)
            if top_k is not None:
                self.slot_topk[slot] = top_k
        stack = lambda f: np.stack([f(c) for _, c in items])
        return {
            "g": self.lane, "slots": slots,
            "qv": stack(lambda c: np.asarray(c.query_vec, np.float32)),
            "ids": stack(lambda c: np.asarray(c.top_ids, np.int32)),
            "dists": stack(lambda c: np.asarray(c.top_dists, np.float32)),
            "exp": stack(lambda c: np.asarray(c.expanded, bool)),
            "vis": stack(lambda c: np.asarray(c.visited, np.int32)),
            "ext": stack(lambda c: np.int32(c.extends)),
            "bud": stack(lambda c: np.int32(getattr(c, "budget", 0))),
        }

    def resume_batch(self, items) -> List[int]:
        if not items:
            return []
        staged = self.stage_resume_batch(items)
        self.group.dispatch_restores([staged])
        return staged["slots"]

    # ------------------------------------------------------ index updates
    def set_index(self, db, graph, corpus_rows: Optional[int] = None):
        self.group.write_lane_index(self.lane, db, graph)
        if corpus_rows is not None:
            self.corpus_n = corpus_rows

    # ------------------------------------------- preemption / checkpoints
    def preempt(self, request_ids) -> List[Tuple[int, SlotCheckpoint]]:
        if not request_ids:
            return []
        slot_of = {rid: slot for slot, rid in self.slot_request.items()}
        slots = [slot_of[rid] for rid in request_ids]
        g_idx, slots_p = self.group._pad_pairs(
            [(self.lane, s) for s in slots])
        self.group.state, rows = evict_slots_group(self.group.state, g_idx,
                                                   slots_p)
        rows = jax.device_get(rows)
        qv, ids, dists, exp, vis, ext, bud = (np.asarray(r) for r in rows)
        out = []
        for i, (rid, slot) in enumerate(zip(request_ids, slots)):
            out.append((rid, SlotCheckpoint(
                query_vec=qv[i].copy(), top_ids=ids[i].copy(),
                top_dists=dists[i].copy(), expanded=exp[i].copy(),
                visited=vis[i].copy(), extends=int(ext[i]),
                budget=int(bud[i]), top_k=self.slot_topk.pop(slot, None))))
            del self.slot_request[slot]
            self.free_slots.append(slot)
        return out

    def snapshot(self, request_ids) -> List[Tuple[int, SlotCheckpoint]]:
        if not request_ids:
            return []
        slot_of = {rid: slot for slot, rid in self.slot_request.items()}
        slots = [slot_of[rid] for rid in request_ids]
        qv, ids, dists, exp, vis, ext, bud = \
            self.group.gather_checkpoint_rows([(self.lane, s)
                                               for s in slots])
        out = []
        for i, (rid, slot) in enumerate(zip(request_ids, slots)):
            out.append((rid, SlotCheckpoint(
                query_vec=qv[i].copy(), top_ids=ids[i].copy(),
                top_dists=dists[i].copy(), expanded=exp[i].copy(),
                visited=vis[i].copy(), extends=int(ext[i]),
                budget=int(bud[i]), top_k=self.slot_topk.get(slot, None))))
        return out

    # ----------------------------------------------------------- stepping
    def collect_completions(self, completed_k: np.ndarray,
                            rows=None, row_offset: int = 0):
        """Turn this lane's (K, R) completion masks into the legacy
        ``step_multi`` tuples. ``rows`` (pre-gathered (ids, dists, ext)
        host arrays starting at ``row_offset``) lets the pool share ONE
        ``collect_rows`` sync across the whole cohort; None gathers just
        this lane's completions."""
        entries = [(i, int(slot)) for i in range(completed_k.shape[0])
                   for slot in np.nonzero(completed_k[i])[0]]
        if rows is None:
            rows = self.group.collect_rows(
                [(self.lane, s) for _, s in entries])
            row_offset = 0
        ids, dists, ext = rows
        out = []
        for j, (i, slot) in enumerate(entries):
            rid = self.slot_request.pop(slot)
            kk = self.slot_topk.pop(slot, self.cfg.top_k)
            r = row_offset + j
            out.append((rid, ids[r, :kk].copy(), dists[r, :kk].copy(),
                        int(ext[r]), i))
            self.free_slots.append(slot)
        return out

    def step_multi(self, num_steps: Optional[int] = None):
        k = self.extend_chunk if num_steps is None else num_steps
        completed_k, tasks_k = self.group.step_lanes([self.lane], k)
        with tracing.span("collect"):
            ck = completed_k[:, self.lane]
            tk = np.ascontiguousarray(tasks_k[:, self.lane])
            self.total_tasks += int(tk.sum())
            out = self.collect_completions(ck) if ck.any() else []
        return out, tk
