#!/usr/bin/env python3
"""The readings the output check's limits are set from: for each seed, one
run of a cell (set-up and a window at the cell's own load, as ``run.py``
makes it), the numbers the check compares for the program's answers, and
the same numbers for the control (the reference search on bfloat16 rows in
the program's place) on the same sampled requests. Both go through the
harness's own comparison: the program has to come out correct and the
control not; the tool exits non-zero where either does not.

  python3 bench/tools/readings.py --workload sift1m-steady \\
      --seconds 6 --seeds 1,2,3

One JSON line per seed on stdout. All seeds run in one process, one after
another, so the compiled programs are shared.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import run as bench_run  # noqa: E402
import spec  # noqa: E402


def read_seed(cell, seed: int, seconds: float, on_chip: bool = True) -> dict:
    """One run of ``cell`` and the judged numbers of program and control."""
    ctx = bench_run.Context(cell=cell, seed=seed, seconds=seconds,
                            trace=None, trace_seconds=0.0, on_chip=on_chip,
                            log=bench_run.log, setup_done=lambda: None)
    served = cell.driver().run(ctx)
    nums, info = served.check()
    ctl = served.control()
    ctl["unfinished"] = 0.0  # the control answers every probe checked
    limits = cell.limits["compare"]
    _, ok = bench_run.judge(nums, limits)
    _, ctl_ok = bench_run.judge(ctl, limits)
    return {"seed": seed, "program": nums, "control": ctl, "correct": ok,
            "control_correct": ctl_ok, "attempted": served.attempted,
            "metrics": served.metrics, "info": info, "served": served.info}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    bench_run.configure_jax()
    import gc

    import jax

    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        sys.exit("needs a TPU")
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        row = read_seed(cell, seed, args.seconds)
        bad += (not row["correct"]) + row["control_correct"]
        print(json.dumps(row), flush=True)
        gc.collect()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
