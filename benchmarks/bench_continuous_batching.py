"""Paper Fig. 3: continuous batching vs per-request batching for graph ANN.

Both engines run the SAME search semantics on the SAME index (recall parity
is a test); what differs is execution:

  per-request — arrivals are grouped into launch windows (batch fills or a
  flush timeout expires), then the whole batch steps in lockstep until the
  LAST query converges. Latency = queue wait + max_extends · t_ext, and
  the operator runs partially empty as queries finish early.

  continuous — Trinity §3.2: finished requests vacate slots immediately,
  newcomers join the next extend's distance batch.

Reported: P50/P95 latency and sustained throughput, across offered
loads.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from benchmarks.common import bench_index, bench_pool_cfg, emit, poisson_arrivals
from repro.core import roofline_model as rm
from repro.core.continuous_batching import ContinuousBatchingEngine
from repro.core.scheduler import VectorRequest
from repro.core.trinity_pool import VectorPool
from repro.vector.cagra import search_batch


def per_request_batched(cfg, db, graph, queries, arrivals, batch_size: int,
                        flush_s: float):
    """Baseline executor: window the stream, lockstep-search each window."""
    t_ext = rm.extend_time(cfg)
    lat = np.zeros(len(arrivals))
    throughput_end = 0.0
    i = 0
    t = 0.0
    dbj, gj = jnp.asarray(db), jnp.asarray(graph)
    while i < len(arrivals):
        j = i
        # window fill: up to batch_size or flush timeout
        while j < len(arrivals) and j - i < batch_size and \
                arrivals[j] <= max(arrivals[i] + flush_s, t):
            j += 1
        start = max(t, arrivals[j - 1])
        q = jnp.asarray(queries[i:j])
        _, _, _, iters = search_batch(
            dbj, gj, q, top_m=cfg.top_m, p=cfg.parents_per_step,
            max_iters=64, num_entries=16, visited_slots=cfg.visited_slots)
        iters = int(iters)
        # every iteration launches a full fixed-shape batch; stragglers
        # keep the whole launch alive
        t = start + iters * t_ext
        lat[i:j] = t - arrivals[i:j]
        throughput_end = t
        i = j
    return lat, len(arrivals) / throughput_end


def continuous(cfg, db, graph, queries, arrivals):
    pool = VectorPool(cfg, db, graph, policy="fifo_shared", use_pallas=False)
    for i, t_arr in enumerate(arrivals):
        pool.submit(VectorRequest(i, "decode", queries[i], float(t_arr),
                                  float(t_arr) + 1.0))
    pool.run_until(float(arrivals[-1]) + 5.0)
    m = pool.metrics
    lat = m.latencies()
    done_t = max(r.t_completed for r in m.completed)
    return lat, len(m.completed) / done_t


def run(emit_rows: bool = True, n_requests: int = 256):
    """Loads are sized relative to the engine's service capacity (≈ slots /
    (extends·t_ext)): 0.1× (sparse/bursty — the paper's 'short, uneven'
    case), 0.5× and 1.5× (overload)."""
    from repro.core import roofline_model as rm

    cfg = bench_pool_cfg()
    db, queries, graph = bench_index(cfg)
    qs = np.tile(queries, (4, 1))[:n_requests]
    capacity = cfg.max_requests / (20.0 * rm.extend_time(cfg))
    rows = []
    out = {}
    for frac in (0.1, 0.5, 1.5):
        qps = frac * capacity
        arr = poisson_arrivals(qps, n_requests, seed=3)
        lat_b, thr_b = per_request_batched(
            cfg, db, graph, qs, arr, batch_size=cfg.max_requests,
            flush_s=2e-3)
        lat_c, thr_c = continuous(cfg, db, graph, qs, arr)
        for name, lat, thr in (("per_request", lat_b, thr_b),
                               ("continuous", lat_c, thr_c)):
            rows += [
                (name, frac, "p50_ms", round(np.percentile(lat, 50) * 1e3, 4)),
                (name, frac, "p95_ms", round(np.percentile(lat, 95) * 1e3, 4)),
                (name, frac, "throughput_qps", round(thr, 1)),
            ]
        out[frac] = {"p95_speedup": np.percentile(lat_b, 95)
                     / max(np.percentile(lat_c, 95), 1e-12)}
    if emit_rows:
        emit(rows, ("engine", "load_frac", "metric", "value"))
    return out


if __name__ == "__main__":
    print(run())
