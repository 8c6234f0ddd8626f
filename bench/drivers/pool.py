"""Driver of the vector pool (``VectorPool.submit`` + ``VectorPool.run_until``)
under open-loop traffic on the host clock.

The pool keeps its own clock per replica (``_Replica.clock``), which the
program advances by an analytic step per fused chunk. The driver holds
that clock to the host clock: before each ``run_until`` it moves every
replica's clock up to the elapsed host time (never back), and asks for
``run_until(now + EPS)`` with EPS below one chunk's step, so each call
steps at most one fused chunk per replica. Flush timers and deadlines so
see real time at the granularity of one chunk. The scheduler's estimate of
a probe's service time, and with it the slack that decides preemption,
still comes from the program's analytic extend time, not from this clock.

Every probe is submitted when it falls due, with ``t_arrival`` its due
time and a deadline of due + its class's deadline. Its latency is the host
time at which the ``run_until`` call that finished it returned (the results
are on the host by then), less its due time. No time the pool reports is
read. A probe that has not finished a minute after the window closed has
failed. A ``run_until`` call that blocks for more than ``SLOW_S`` is
reported on stderr with its time, queue and active slots.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import ann_ref
import arrivals
import corpus
from hostclock import GcPauses, now, span

from repro.configs.base import VectorPoolConfig
from repro.core.scheduler import VectorRequest
from repro.core.trinity_pool import VectorPool

EPS = 1e-6  # s; below one fused chunk's analytic clock step
DRAIN_S = 60.0  # how long past the window an unfinished probe is awaited
WARM_S = 1.0  # s of the cell's own traffic through the pool in set-up
WARM_RID = 1 << 27  # rid base of warm-up requests (window probes: 0..n-1)
SLOW_S = 0.1  # a run_until call longer than this is reported
CHECK_MAX = 2048  # answers compared per run, a sample drawn from the seed
COUNTERS = ("extend_steps", "tasks_emitted", "tasks_capacity",
            "preemptions", "resumes")


def seeds_of(seed: int) -> Dict[str, int]:
    """Independent 31-bit seeds of each random stream of a run."""
    names = ("data", "graph", "traffic", "queries", "warm", "pool", "check")
    kids = np.random.SeedSequence(int(seed) % (1 << 64)).spawn(len(names))
    return {n: int(k.generate_state(1)[0] & 0x7FFFFFFF)
            for n, k in zip(names, kids)}


def pool_config(config: Dict) -> VectorPoolConfig:
    return VectorPoolConfig(**config["pool"])


def build(config: Dict, seeds: Dict[str, int],
          log: Callable[[str], None] = lambda m: None):
    """(corpus generator, db, graph) of a configuration and seed; db and
    graph on the host."""
    cfg = pool_config(config)
    log("making the corpus and its graph")
    data = corpus.ClusteredData(seeds["data"], cfg.dim,
                                config["data"]["clusters"],
                                config["data"]["sigma"])
    db_dev = data.device_rows(cfg.num_vectors, seeds["data"] + 1)
    graph = corpus.search_graph(db_dev, cfg.graph_degree,
                                config["graph"]["long_edges"],
                                np.random.default_rng(seeds["graph"]), log)
    db = np.asarray(db_dev)
    del db_dev
    return data, db, graph


class Loop:
    """The real-clock loop around one pool."""

    def __init__(self, pool: VectorPool):
        self.pool = pool
        self.base = now()  # host time of pool time 0
        self.seen = len(pool.metrics.completed)
        self.slow = []  # (at, seconds, queued, active) of calls over SLOW_S

    def t(self) -> float:
        return now() - self.base

    def step(self) -> List[VectorRequest]:
        """One ``run_until`` at the host time; the requests it finished."""
        t = self.t()
        for rep in self.pool.replicas:
            if rep.clock < t:
                rep.clock = t
        with span("run_until"):
            self.pool.run_until(t + EPS)
        took = self.t() - t
        if took > SLOW_S:
            self.slow.append((t, took, self.pool.scheduler.queued(),
                              self.pool.replicas[0].engine.num_active))
        done = self.pool.metrics.completed
        new = done[self.seen:]
        self.seen = len(done)
        return new

    def serve(self, kinds: List[str], qvecs: np.ndarray, due: np.ndarray,
              t0: float, seconds: float, cfg, rid0: int,
              done_at: Dict[int, float], late: List[float],
              on_tick: Optional[Callable[[float], float]] = None
              ) -> List[VectorRequest]:
        """Submit probe j (rid ``rid0 + j``) when it falls due, at
        ``t0 + due[j]``, and step the pool until every one has finished or
        ``DRAIN_S`` past the window's close. Returns the requests, in
        order. ``done_at[rid]``: the time, on the pool's time base, at which
        the call that finished ``rid`` returned. ``on_tick(t)`` may return
        a pause in seconds: every probe not yet due falls due that much
        later (the profiler's stop stalls the host for seconds)."""
        ddl = {"prefill": cfg.prefill_deadline_ms / 1e3,
               "decode": cfg.decode_deadline_ms / 1e3}
        i, n, outstanding = 0, len(kinds), 0
        reqs: List[VectorRequest] = []
        while True:
            t = self.t()
            if on_tick is not None:
                t0 += on_tick(t) or 0.0
            if i < n and t0 + due[i] <= t:
                with span("submit"):
                    while i < n and t0 + due[i] <= t:
                        a = t0 + float(due[i])
                        req = VectorRequest(rid0 + i, kinds[i], qvecs[i], a,
                                            a + ddl[kinds[i]])
                        self.pool.submit(req)
                        reqs.append(req)
                        late.append(t - a)
                        i += 1
                        outstanding += 1
            elif outstanding == 0:
                if i == n:
                    return reqs
                with span("wait_arrival"):
                    gap = t0 + due[i] - self.t()
                    if gap > 1e-3:
                        time.sleep(gap - 5e-4)
                continue
            if t > t0 + seconds + DRAIN_S:
                return reqs + [None] * (n - i)
            t_ret = None
            for req in self.step():
                if rid0 <= req.rid < rid0 + n:
                    t_ret = self.t() if t_ret is None else t_ret
                    done_at[req.rid] = t_ret
                    outstanding -= 1


def warm_engine(engine, qvecs):
    """Compile every shape the window can use: admissions, evictions and
    restores of each power-of-two batch up to the slot count, and the
    fused extend, through the engine's own calls."""
    rid, b = WARM_RID // 2, 1
    while b <= engine.cfg.max_requests:
        reqs = [(rid + j, qvecs[j % len(qvecs)], None) for j in range(b)]
        rid += b
        engine.admit_batch(reqs)
        engine.resume_batch(engine.preempt([r[0] for r in reqs]))
        while engine.num_active:
            engine.step_multi()
        b *= 2


def counters(pool) -> Dict[str, int]:
    return {k: int(getattr(pool.metrics, k)) for k in COUNTERS}


def delta(a: Dict[str, int], b: Dict[str, int]) -> Dict[str, int]:
    return {k: b[k] - a[k] for k in a}


@dataclasses.dataclass
class Served:
    """What one window served, and the check of its answers."""

    attempted: int
    failed: int
    metrics: Dict[str, float]  # end-to-end, host clock
    counters: Dict[str, float]  # program counters over the window
    traced: Dict[str, float]  # the same over its traced first part
    info: Dict[str, float]
    check: Callable[[], tuple]  # () -> ({name: value}, info)
    control: Callable[[], dict]  # after check: the control's numbers


def run(ctx) -> Served:
    cell, config = ctx.cell, ctx.cell.config
    cfg = pool_config(config)
    seeds = seeds_of(ctx.seed)
    data, db, graph = build(config, seeds, ctx.log)
    ctx.log(f"corpus {db.shape} and graph {graph.shape} built")
    pool = VectorPool(cfg, db, graph, policy=config["policy"],
                      seed=seeds["pool"])
    if ctx.on_chip and not pool.replicas[0].engine.use_pallas:
        raise RuntimeError("the engine did not choose the Pallas kernel")

    arr = arrivals.open_loop(cell.traffic, ctx.seconds,
                             np.random.default_rng(seeds["traffic"]))
    queries = data.rows(len(arr), seeds["queries"])
    ctx.log("pool built")
    warm_engine(pool.replicas[0].engine, queries[:64])
    ctx.log("engine programs warmed")
    loop = Loop(pool)
    warm = arrivals.open_loop(cell.traffic, WARM_S,
                              np.random.default_rng(seeds["warm"]))
    done: Dict[int, float] = {}
    wreqs = loop.serve(warm.kind, data.rows(len(warm), seeds["warm"]),
                       warm.due_s, loop.t(), WARM_S, cfg, WARM_RID, done, [])
    if len(done) != len(wreqs):
        raise RuntimeError("warm-up probes did not finish")
    ctx.log(f"warm-up: {len(wreqs)} probes served")

    gc.collect()
    gc.freeze()
    trace = ctx.trace
    traced: Dict[str, Dict[str, int]] = {}
    if trace is not None:
        # the trace covers the first trace_seconds of the window: started
        # before the window opens, stopped inside it, and the probes not
        # yet due wait out the stop's stall
        trace.start()
        traced["c0"] = counters(pool)
    ctx.setup_done()
    t0 = loop.t()
    done_at: Dict[int, float] = {}
    late: List[float] = []

    def on_tick(t):
        if trace is not None and trace.active \
                and t >= t0 + ctx.trace_seconds:
            traced["c1"] = counters(pool)
            trace.stop()
            pause = loop.t() - t
            ctx.log(f"profiler stopped in {pause:.3f} s; arrivals paused")
            return pause
        return 0.0

    c0 = counters(pool)
    loop.slow.clear()
    with GcPauses() as gcp:
        reqs = loop.serve(arr.kind, queries, arr.due_s, t0, ctx.seconds, cfg,
                          0, done_at, late, on_tick)
    if trace is not None and trace.active:
        traced["c1"] = counters(pool)
        trace.stop()
    c1 = counters(pool)
    t_end = loop.t()
    gc.unfreeze()

    lat = {"prefill": [], "decode": []}
    for j, r in enumerate(reqs):
        a = t0 + arr.due_s[j] if r is None else r.t_arrival
        lat[arr.kind[j]].append(done_at.get(j, t_end) - a)
    unfinished = len(reqs) - len(done_at)
    metrics = {f"{k}_probe_p95_ms": float(np.percentile(v, 95)) * 1e3
               for k, v in lat.items() if v}
    info = {f"{k}_probe_p50_ms": float(np.median(v)) * 1e3
            for k, v in lat.items() if v}
    info.update(generator_late_p95_ms=float(np.percentile(late, 95)) * 1e3,
                generator_late_max_ms=float(np.max(late)) * 1e3,
                gc_pause_max_ms=max([p for _, p in gcp.pauses], default=0.0)
                * 1e3,
                gc_full_collections=sum(g == 2 for g, _ in gcp.pauses),
                slow_run_until_calls=len(loop.slow))
    for at, took, queued, active in sorted(loop.slow, key=lambda x: -x[1])[:5]:
        ctx.log(f"slow run_until at {at - t0:.3f} s of the window: "
                f"{took * 1e3:.1f} ms, {queued} queued, {active} active")
    window = dict(delta(c0, c1), probes=len(reqs), dim=cfg.dim)
    traced_c = {}
    if "c1" in traced:
        traced_c = dict(delta(traced["c0"], traced["c1"]), dim=cfg.dim,
                        probes=int(np.sum(arr.due_s < ctx.trace_seconds)))

    checked = {}

    def check():
        """The answers the window returned, at most ``CHECK_MAX`` of them
        drawn from the seed, against the reference."""
        nonlocal pool, loop
        pick = np.array(sorted(done_at), np.int64)
        if len(pick) > CHECK_MAX:
            pick = np.sort(np.random.default_rng(seeds["check"]).choice(
                pick, CHECK_MAX, replace=False))
        info = {"checked": int(len(pick)),
                "preempted_checked": int(sum(
                    reqs[i].preemptions > 0 for i in pick))}
        if len(pick) == 0:  # nothing answered: every answer is missing
            return {"id_mismatch": 1.0, "dist_err": 0.0,
                    "unfinished": float(unfinished)}, info
        got_ids = np.stack([np.asarray(reqs[i].result_ids) for i in pick])
        got_d = np.stack([np.asarray(reqs[i].result_dists) for i in pick])
        del pool, loop
        gc.collect()
        ref = reference(config, db, graph, queries[pick], pick,
                        seeds["pool"])
        checked.update(pick=pick, ref=ref)
        nums = numbers(db, queries[pick], got_ids, got_d, ref)
        nums["unfinished"] = float(unfinished)
        return nums, info

    def control():
        """The control's numbers on the requests ``check`` compared: the
        reference search on bfloat16 rows in the program's place."""
        pick = checked["pick"]
        ctl = reference(config, db, graph, queries[pick], pick,
                        seeds["pool"], precision="bfloat16")
        return numbers(db, queries[pick], ctl[0], ctl[1], checked["ref"])

    return Served(attempted=len(reqs), failed=unfinished, metrics=metrics,
                  counters=window, traced=traced_c, info=info, check=check,
                  control=control)


def reference(config, db, graph, queries, rids, pool_seed,
              precision: str = "float32"):
    """The reference search's (ids, dists) for requests ``rids``."""
    p = config["pool"]
    ent = ann_ref.entry_points(pool_seed, rids, p["num_vectors"],
                               config["search"]["entry_points"])
    ids, dists, _ = ann_ref.search(
        db, graph, queries, ent, top_m=p["top_m"], p=p["parents_per_step"],
        visited_slots=p["visited_slots"], top_k=p["top_k"],
        precision=precision)
    return ids, dists


def numbers(db, queries, got_ids, got_d, ref) -> Dict[str, float]:
    """The numbers compared for answers ``got_*``: the share of answered
    ids that the reference search does not answer, and the largest
    relative error of an answered distance against float64."""
    ref_ids = ref[0]
    miss = sum(len(set(g.tolist()) - set(r.tolist()))
               for g, r in zip(got_ids, ref_ids))
    exact = ann_ref.exact_distances(db, queries, got_ids)
    rel = np.abs(got_d.astype(np.float64) - exact) / np.maximum(exact, 1e-12)
    return {"id_mismatch": miss / got_ids.size, "dist_err": float(rel.max())}
