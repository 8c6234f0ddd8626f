#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic come from ``BENCHMARK.json``
and the files it names (``bench/spec.py``). One process: set-up (data,
index, the system under test, warm-up of every shape the window uses),
one measured window of ``--seconds`` on the host clock, then the check of
what the window produced against a plain reference. With ``--trace 1`` the
first ``TRACE_SECONDS`` of the window are traced by the profiler and the
cell's per-layer metrics are reported instead of its end-to-end ones.

Needs a TPU: with no TPU, or fewer chips than the cell asks for, it exits
non-zero and prints no result. The last line of stdout is one JSON object
(correct, attempted, failed, metrics, device, [breakdown], check); the
last lines of stderr are the numbers compared, each beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Callable, Dict, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import spec  # noqa: E402

CACHE = os.path.join(ROOT, ".bench_cache")
TRACE_SECONDS = 3.0  # at most this much of the window is traced


def log(msg: str):
    """A line on stderr, stamped with the seconds since the process began."""
    print(f"[bench] {time.perf_counter() - T_START:8.3f} {msg}",
          file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's arguments, and hooks."""

    cell: spec.Cell
    seed: int
    seconds: float
    trace: Optional[object]  # hostclock.WindowTrace, or None
    trace_seconds: float
    on_chip: bool
    log: Callable[[str], None]
    setup_done: Callable[[], None]


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader reads."""

    counters: Dict[str, float]
    traced: Dict[str, float]
    trace: Optional[object]  # trace_reduce.Reduced
    peak: Dict[str, float]


def configure_jax():
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CACHE, "jax"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def judge(nums: Dict[str, float], limits: Dict[str, float]):
    """Each compared number beside its limit, and whether all are within
    (a number at most its limit)."""
    check = {k: {"value": float(nums[k]), "limit": float(v)}
             for k, v in limits.items()}
    return check, all(c["value"] <= c["limit"] for c in check.values())


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             on_chip: bool, t_start: float, peak: Dict[str, float]) -> dict:
    """Set up, serve one window, check it; the result object."""
    import jax

    import hostclock
    import trace_reduce

    marks = {}
    compiles = hostclock.CompileCount()
    tracer = (hostclock.WindowTrace(os.path.join(CACHE, "trace"))
              if trace else None)
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=tracer,
                  trace_seconds=min(TRACE_SECONDS, seconds / 2),
                  on_chip=on_chip, log=log,
                  setup_done=lambda: marks.setdefault("window",
                                                      hostclock.now()))
    served = cell.driver().run(ctx)
    in_window = compiles.since(marks["window"])
    served.info["compiles_in_window"] = len(in_window)
    for at, event, took in in_window[:20]:
        log(f"compile event {at - marks['window']:.3f} s into the window: "
            f"{event} {took * 1e3:.1f} ms")
    used = jax.devices()[:cell.chips]
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in used)
    nums, check_info = served.check()

    limits = cell.limits["compare"]
    check, correct = judge(nums, limits)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": mem}
    metrics = {}
    out = {}
    if not trace:
        values = dict(served.metrics, setup_s=marks["window"] - t_start)
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise KeyError(f"the driver reported no {m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    else:
        reduced = trace_reduce.reduce(trace_reduce.load(tracer.path))
        tracer.discard()
        run = Run(counters=served.counters, traced=served.traced,
                  trace=reduced, peak=peak)
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        out["breakdown"] = reduced.breakdown()
        log("programs in the traced window: " + json.dumps(
            {k: [v, reduced.module_calls[k]]
             for k, v in sorted(reduced.program_s.items(),
                                key=lambda kv: -kv[1])[:8]}))

    for k, v in sorted(dict(served.info, **check_info).items()):
        log(f"info {k} {v}")
    for k, v in sorted(nums.items()):
        if k not in limits:
            log(f"info {k} {v}")
    for k, c in check.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return dict({"correct": correct, "attempted": served.attempted,
                 "failed": served.failed, "metrics": metrics,
                 "device": device}, **out, check=check)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    configure_jax()
    import jax

    import work

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"needs a TPU; JAX found {devs[0].platform!r}")
        return 2
    if len(devs) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips; JAX found {len(devs)}")
        return 2
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), on_chip=True, t_start=T_START,
                      peak=work.peaks(devs[0].device_kind))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
