"""Benchmark harness entry point: one benchmark per paper figure/table.

``PYTHONPATH=src python -m benchmarks.run [--only NAME]``

Emits per-figure CSV blocks plus a final ``name,us_per_call,derived``
summary line per benchmark (harness contract).
"""
from __future__ import annotations

import argparse
import time

from benchmarks import (bench_architectures, bench_autoscale, bench_chaos,
                        bench_continuous_batching, bench_dispatch_pipeline,
                        bench_engine_dispatch, bench_preemption,
                        bench_rebalance, bench_recall_latency,
                        bench_roofline_stages, bench_scheduler,
                        bench_semantic_cache, bench_sharded)
from repro.launch.compile_cache import enable_compile_cache

BENCHES = {
    "fig1_roofline_stages": bench_roofline_stages.run,
    "fig2_architectures": bench_architectures.run,
    "fig3_continuous_batching": bench_continuous_batching.run,
    "fig4_scheduler": bench_scheduler.run,
    "supp_recall_latency": bench_recall_latency.run,
    "supp_engine_dispatch": bench_engine_dispatch.run,
    "supp_preemption": bench_preemption.run,
    "supp_semantic_cache": bench_semantic_cache.run,
    "supp_sharded": bench_sharded.run,
    "supp_rebalance": bench_rebalance.run,
    "supp_chaos": bench_chaos.run,
    "supp_dispatch": bench_dispatch_pipeline.run,
    "supp_autoscale": bench_autoscale.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=list(BENCHES), default=None)
    args = ap.parse_args()
    enable_compile_cache()

    summary = []
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        print(f"\n=== {name} " + "=" * (60 - len(name)))
        t0 = time.time()
        derived = fn(emit_rows=True)
        us = (time.time() - t0) * 1e6
        summary.append((name, us, derived))
    print("\nname,us_per_call,derived")
    for name, us, derived in summary:
        print(f"{name},{us:.0f},\"{derived}\"")


if __name__ == "__main__":
    main()
