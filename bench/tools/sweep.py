#!/usr/bin/env python3
"""Find the pool's knee: serve a cell's traffic mix at a list of fixed
rates on one pool, a window each, and report per rate the share of probes that met
their stage deadline, the tails, and whether the backlog grew (median
latency of the window's last quarter against its first).

  python3 bench/tools/sweep.py --workload sift1m-steady --seed 11 \\
      --seconds 6 --rates 100,200,300

The knee is the highest rate at which at least 90% of decode-stage probes
meet their deadline and the backlog does not grow (prefill-stage probes
are reported beside). The sweep stops at the first rate
whose backlog grows or whose decode probes mostly miss. One JSON line per rate
on stdout.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

import arrivals  # noqa: E402
import spec  # noqa: E402
from run import configure_jax  # noqa: E402

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    configure_jax()
    import jax

    cell = spec.load_cell(args.workload)
    pool_drv = cell.driver()
    config = cell.config
    cfg = pool_drv.pool_config(config)
    seeds = pool_drv.seeds_of(args.seed)
    data, db, graph = pool_drv.build(config, seeds)
    pool = pool_drv.VectorPool(cfg, db, graph, policy=config["policy"],
                               seed=seeds["pool"])
    print(f"# device {jax.devices()[0].device_kind} use_pallas "
          f"{pool.replicas[0].engine.use_pallas}", file=sys.stderr)
    pool_drv.warm_engine(pool.replicas[0].engine,
                         data.rows(64, seeds["warm"]))
    loop = pool_drv.Loop(pool)
    rid0 = 0
    ddl = {"prefill": cfg.prefill_deadline_ms, "decode": cfg.decode_deadline_ms}
    for n, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        arr = arrivals.open_loop(mix, args.seconds,
                                 np.random.default_rng(seeds["traffic"] + n))
        queries = data.rows(len(arr), seeds["queries"] + n)
        t0 = loop.t() + 0.05
        done, late = {}, []
        c0 = pool_drv.counters(pool)
        reqs = loop.serve(arr.kind, queries, arr.due_s, t0, args.seconds, cfg,
                          rid0, done, late)
        rid0 += len(reqs)
        c1 = pool_drv.counters(pool)
        t_end = loop.t()
        row = {"rate": rate, "probes": len(reqs),
               "unfinished": len(reqs) - len(done)}
        for k in ("prefill", "decode"):
            rs = [r for r in reqs if r is not None and r.kind == k]
            if not rs:
                continue
            lat = np.array([done.get(r.rid, t_end) - r.t_arrival
                            for r in rs]) * 1e3
            q = len(lat) // 4
            row[k] = {"met": float(np.mean(lat <= ddl[k])),
                      "p50": float(np.median(lat)),
                      "p95": float(np.percentile(lat, 95)),
                      "p99": float(np.percentile(lat, 99)),
                      "p50_first_q": float(np.median(lat[:q])),
                      "p50_last_q": float(np.median(lat[-q:]))}
        row["counters"] = pool_drv.delta(c0, c1)
        row["late_p95_ms"] = float(np.percentile(late, 95)) * 1e3
        print(json.dumps(row), flush=True)
        d = row.get("decode", {})
        if row["unfinished"] or d.get("met", 1.0) < 0.5 \
                or d.get("p50_last_q", 0.0) > 4 * d.get("p50_first_q", 1.0):
            break


if __name__ == "__main__":
    main()
