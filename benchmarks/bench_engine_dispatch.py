"""Engine dispatch-overhead benchmark: per-step vs fused-K stepping, and
matmul-onehot vs slot-gather distance.

Quantifies the two hot-path costs the fused engine kills:

  1. host-device round trips — the per-step loop pays one jitted dispatch
     plus a completion-mask readback per extend; ``step_multi`` runs K
     extends under one ``lax.scan`` dispatch and syncs once per chunk.
     Reported as wall-clock µs per extend step draining the same workload.

  2. distance-stage FLOPs — the matmul+one-hot formula does O(TB·R·d)
     work to use O(TB·d) of it; the slot-gather formula gathers the owning
     query row per task and reduces row-wise. Reported as µs per call at
     the engine's fixed task shape, for the kernel and both jnp forms.

Emits a machine-readable ``BENCH_engine.json`` next to this file (override
with ``--out``) and the usual CSV rows via the harness contract.

``PYTHONPATH=src python -m benchmarks.bench_engine_dispatch``
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import time

import jax
import numpy as np

from benchmarks.common import bench_index, bench_pool_cfg, emit
from repro.core.continuous_batching import ContinuousBatchingEngine
from repro.kernels import ops

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "BENCH_engine.json")


def _drain_legacy(engine, queries, n):
    """The pre-fusion hot loop, reconstructed faithfully: one jitted
    ``admit`` dispatch per request, one raw ``extend_step`` dispatch per
    extend with its per-step ``np.asarray(completed)`` / ``int(tasks)``
    readbacks and completion-state pulls, and a device-side active-count
    sync (`int(jnp.sum(active))`) per iteration — exactly the host↔device
    chatter the fused path eliminates. Returns extend steps executed."""
    import jax.numpy as jnp

    from repro.core.continuous_batching import extend_step

    cfg = engine.cfg
    for i in range(n):
        engine.admit(i, queries[i])
    steps = 0
    while int(jnp.sum(engine.state.active)):
        # the seed engine's step() opened with a live-slot count,
        # `int(jnp.sum(active))` — a second device reduction+sync per
        # extend
        _ = int(jnp.sum(engine.state.active))
        engine.state, completed, tasks = extend_step(
            engine.state, engine.db, engine.graph,
            p=cfg.parents_per_step, task_batch=cfg.task_batch,
            use_pallas=engine.use_pallas, metric=cfg.metric,
            distance_mode=engine.distance_mode)
        completed = np.asarray(completed)
        _ = int(tasks)
        if completed.any():  # old step(): pull result state per completion
            _ = (np.asarray(engine.state.top_ids),
                 np.asarray(engine.state.top_dists),
                 np.asarray(engine.state.extends))
        steps += 1
    engine.slot_request.clear()  # host bookkeeping bypassed above
    return steps


def _drain_per_step(engine, queries, n):
    """Per-step dispatch with the host-side bookkeeping fixes only (batched
    admission, no device active-count poll) — isolates the scan fusion."""
    engine.admit_batch([(i, queries[i]) for i in range(n)])
    steps = 0
    while engine.num_active:
        engine.step()
        steps += 1
    return steps


def _drain_fused(engine, queries, n, k):
    engine.admit_batch([(i, queries[i]) for i in range(n)])
    steps = 0
    while engine.num_active:
        engine.step_multi(k)
        steps += k
    return steps


def bench_stepping(cfg, db, graph, queries, chunks=(4, 8), rounds: int = 7):
    """µs of wall-clock per extend step, draining the same admitted batch.

    Rounds are interleaved across variants (round-robin) and reduced with
    min — the shared box drifts under external load, and interleaving keeps
    a slow phase from penalising one variant only."""
    n = cfg.max_requests
    arms = [("legacy_per_step", lambda e: _drain_legacy(e, queries, n)),
            ("per_step", lambda e: _drain_per_step(e, queries, n))] \
        + [(f"fused_k{k}", (lambda k: lambda e: _drain_fused(
            e, queries, n, k))(k)) for k in chunks]
    round_us = {label: [] for label, _ in arms}
    steps = {}
    for label, fn in arms:  # warmup: compile every jitted shape on the path
        fn(ContinuousBatchingEngine(cfg, db, graph, use_pallas=False, seed=0))
    for r in range(rounds):
        for label, fn in arms:
            eng = ContinuousBatchingEngine(cfg, db, graph, use_pallas=False,
                                           seed=0)
            t0 = time.perf_counter()
            steps[label] = fn(eng)
            round_us[label].append(
                (time.perf_counter() - t0) / steps[label] * 1e6)
    results = {label: {"us_per_extend": min(us),
                       "us_per_extend_rounds": [round(u, 1) for u in us],
                       "extends": steps[label]}
               for label, us in round_us.items()}
    legacy = results["legacy_per_step"]["us_per_extend"]
    base = results["per_step"]["us_per_extend"]
    for k in chunks:
        r = results[f"fused_k{k}"]
        r["speedup_vs_per_step"] = base / r["us_per_extend"]
        r["speedup_vs_legacy_per_step"] = legacy / r["us_per_extend"]
    return results


def bench_distance_modes(cfg, db, queries_rows, rounds: int = 30):
    """µs per distance_tasks call at the engine's fixed task shape."""
    rng = np.random.default_rng(17)
    R = cfg.max_requests
    T = cfg.task_batch
    dbj = jax.numpy.asarray(db)
    qj = jax.numpy.asarray(queries_rows[:R])
    ids = jax.numpy.asarray(rng.integers(0, len(db), T, dtype=np.int32))
    slot = jax.numpy.asarray(rng.integers(0, R, T, dtype=np.int32))
    results = {}
    # the Pallas kernel (interpret mode on CPU — the per-row DMA emulation
    # adds overhead there) and the jnp oracles of both formulas (pure
    # XLA:CPU, the honest CPU FLOP comparison; the one-hot form has no
    # kernel).
    from repro.kernels import ref as kernel_ref
    corpus = ops.corpus_layout(dbj)
    variants = {
        "slot_gather": lambda: ops.distance_tasks(corpus, qj, ids, slot),
        "matmul_onehot_jnp": jax.jit(functools.partial(
            kernel_ref.distance_tasks_onehot_ref, dbj, qj, ids, slot)),
        "slot_gather_jnp": jax.jit(functools.partial(
            kernel_ref.distance_tasks_ref, dbj, qj, ids, slot)),
    }
    for name, fn in variants.items():
        out = fn()  # compile
        out.block_until_ready()
        blocks = []
        for _ in range(5):  # best-of-5 blocks of `rounds` calls
            t0 = time.perf_counter()
            for _ in range(rounds):
                out = fn()
            out.block_until_ready()
            blocks.append((time.perf_counter() - t0) / rounds * 1e6)
        results[name] = {"us_per_call": min(blocks)}
    results["slot_gather_jnp"]["speedup_vs_matmul_onehot"] = \
        results["matmul_onehot_jnp"]["us_per_call"] \
        / results["slot_gather_jnp"]["us_per_call"]
    return results


def run(emit_rows: bool = True, out_path: str = DEFAULT_OUT):
    cfg = bench_pool_cfg()
    db, queries, graph = bench_index(cfg)
    stepping = bench_stepping(cfg, db, graph, queries)
    distance = bench_distance_modes(cfg, db, queries)

    report = {
        "config": {k: v for k, v in dataclasses.asdict(cfg).items()
                   if not isinstance(v, (list, tuple, dict))},
        "backend": jax.default_backend(),
        "stepping": stepping,
        "distance": distance,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)

    rows = []
    for name, r in stepping.items():
        for metric in ("us_per_extend", "speedup_vs_per_step",
                       "speedup_vs_legacy_per_step"):
            if metric in r:
                rows.append(("stepping", name, metric, round(r[metric], 3)))
    for name, r in distance.items():
        for metric in ("us_per_call", "speedup_vs_matmul_onehot"):
            if metric in r:
                rows.append(("distance", name, metric, round(r[metric], 3)))
    if emit_rows:
        emit(rows, ("stage", "variant", "metric", "value"))
    return {"fused_k4_speedup_vs_legacy":
            stepping["fused_k4"]["speedup_vs_legacy_per_step"],
            "fused_k8_speedup_vs_legacy":
            stepping["fused_k8"]["speedup_vs_legacy_per_step"],
            "fused_k8_speedup_vs_per_step":
            stepping["fused_k8"]["speedup_vs_per_step"],
            "slot_gather_speedup":
            distance["slot_gather_jnp"]["speedup_vs_matmul_onehot"],
            "json": out_path}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args()
    print(run(out_path=args.out))
