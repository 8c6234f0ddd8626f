#!/usr/bin/env python3
"""Where one fused chunk's time goes: the pool's own host spans
(``trinity.*``, ``repro.core.tracing``) and the extend program's named
scopes, on the chip, at a cell's own load.

  python3 bench/tools/chunk_spans.py --workload sift1m-steady --seed 11 \\
      --seconds 10 --windows 3,0.25 --out spans_out

Set-up as ``run.py`` makes it (data, graph, pool, warm-up). Then one
untraced window of ``--seconds`` gives each span's self time per chunk
with no profiler session, slow ``run_until`` calls with the span that held
most of them, and the cost of a span with no session. Then each traced
window of ``--windows`` (seconds each) is reduced from its trace:

- each idle gap of device 0 goes to the innermost host span covering it
  (``bench.*`` under the name less its prefix, ``trinity.*`` whole);
- each operation that ran inside a ``jit_extend_multi`` execution goes to
  the named scope of its HLO instruction (``op_name`` metadata of the
  program compiled for the engine's own arguments, a cache hit);
- the lead of each execution's start over its ``trinity.dispatch`` span
  (below zero where the trace's device clock runs behind its host clock);
- the queue wait per stage over the window, from ``PoolMetrics``.

One JSON line per window on stdout. Under ``--out``: each window's trace,
gzipped, and ``scopes.json``, the scope of each instruction.
"""
import argparse
import gzip
import json
import os
import re
import shutil
import sys
import time
from collections import defaultdict

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

import arrivals  # noqa: E402
import spec  # noqa: E402
import trace_reduce  # noqa: E402
from run import configure_jax  # noqa: E402

SCOPES = ("build_tasks", "pad_tasks", "distance", "merge_topm", "converge")
PROGRAM = "jit_extend_multi"
HOST_SPANS = ("bench.", "trinity.")
_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.-]+) = .*?op_name="([^"]*)"')


def innermost(spans):
    """Disjoint sorted pieces ``(start, end, name)`` of the host spans,
    each piece under the innermost span covering it: the one opened last
    among those open there (spans of one thread nest)."""
    import heapq

    bounds = sorted({t for e in spans for t in (e.start_ns, e.end_ns)})
    order = sorted(spans, key=lambda e: (e.start_ns, -e.end_ns))
    heap, j, out = [], 0, []
    for a, b in zip(bounds, bounds[1:]):
        while j < len(order) and order[j].start_ns <= a:
            e = order[j]
            heapq.heappush(heap, (-e.start_ns, e.end_ns, j, e.name))
            j += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            name = heap[0][3]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def span_key(name: str) -> str:
    return name[len("bench."):] if name.startswith("bench.") else name


def attribute(gaps, spans):
    """Seconds of ``gaps`` by the innermost span covering them; the rest
    under ``other``."""
    out = defaultdict(float)
    pieces = innermost(spans)
    j = 0
    for gs, ge in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            ov = min(ge, pieces[k][1]) - max(gs, pieces[k][0])
            if ov > 0:
                out[span_key(pieces[k][2])] += ov / 1e9
                covered += ov
            k += 1
        if ge - gs - covered > 0:
            out["other"] += (ge - gs - covered) / 1e9
    return dict(out)


def scope_of(hlo_text: str):
    """HLO instruction name -> the first of ``SCOPES`` in its op_name."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            parts = m.group(2).split("/")
            out[m.group(1)] = next((p for p in parts if p in SCOPES), "")
    return out


def program_ops(planes, lo, hi, scopes, program=PROGRAM):
    """Device 0's operations inside executions of ``program`` within
    [lo, hi): (seconds by scope, seconds by "scope/op type", seconds of
    the executions, executions). Control flow is left out, as in
    ``trace_reduce``; an operation of no scope counts under ``""``."""
    dev = next(p for p in planes if trace_reduce.DEVICE_PLANE.match(p.name))
    runs = sorted((e.start_ns, e.end_ns)
                  for e in dev.lines.get(trace_reduce.MODULES_LINE, [])
                  if trace_reduce.program_name(e.name) == program
                  and lo <= e.start_ns < hi)
    by_scope, by_op = defaultdict(float), defaultdict(float)
    ops = sorted(dev.lines.get(trace_reduce.OPS_LINE, []),
                 key=lambda e: e.start_ns)
    j = 0
    for s, e in runs:
        while j < len(ops) and ops[j].start_ns < s:
            j += 1
        while j < len(ops) and ops[j].start_ns < e:
            op = ops[j]
            j += 1
            if trace_reduce.op_name(op.name)[1] in trace_reduce.CONTROL:
                continue
            detail = trace_reduce.op_detail(op.name)
            sc = scopes.get(detail.split(" ")[0], "")
            sec = (min(op.end_ns, e) - op.start_ns) / 1e9
            by_scope[sc] += sec
            by_op[f"{sc}/{detail}"] += sec
    return (dict(by_scope), dict(by_op),
            sum(e - s for s, e in runs) / 1e9, len(runs))


def clock_offsets(planes, spans, program=PROGRAM):
    """Quartiles, in ms, of each ``trinity.dispatch`` span's start to the
    start of the nearest ``program`` execution on device 0, and of that
    execution's end to the end of the next ``trinity.sync`` span. The
    first is below zero where the device's clock runs behind the host's
    in the trace: an execution cannot start before its dispatch."""
    import bisect

    dev = next(p for p in planes if trace_reduce.DEVICE_PLANE.match(p.name))
    runs = sorted((e.start_ns, e.end_ns)
                  for e in dev.lines.get(trace_reduce.MODULES_LINE, [])
                  if trace_reduce.program_name(e.name) == program)
    syncs = sorted(e.end_ns for e in spans if e.name == "trinity.sync")
    starts = [s for s, _ in runs]
    lead, tail = [], []
    for d in (e for e in spans if e.name == "trinity.dispatch"):
        i = bisect.bisect_left(starts, d.start_ns)
        i = min((j for j in (i - 1, i) if 0 <= j < len(runs)),
                key=lambda j: abs(starts[j] - d.start_ns), default=None)
        if i is None:
            continue
        lead.append((runs[i][0] - d.start_ns) / 1e6)
        k = bisect.bisect_left(syncs, runs[i][1])
        if k < len(syncs):
            tail.append((syncs[k] - runs[i][1]) / 1e6)
    q = lambda xs: np.percentile(xs, [25, 50, 75]).tolist() if xs else None
    return {"dispatch_to_program_ms": q(lead),
            "program_end_to_sync_end_ms": q(tail)}


def host_spans(planes, lo, hi):
    """The bench.* and trinity.* host spans inside [lo, hi), the window
    span left out."""
    return [trace_reduce.Event(e.name, max(e.start_ns, lo),
                               min(e.end_ns, hi))
            for p in planes if not trace_reduce.DEVICE_PLANE.match(p.name)
            for evs in p.lines.values() for e in evs
            if e.name.startswith(HOST_SPANS)
            and e.name != trace_reduce.WINDOW_SPAN
            and e.end_ns > lo and e.start_ns < hi]


def reduce_window(planes, scopes):
    """The breakdown of one traced window (see the module docstring)."""
    red = trace_reduce.reduce(planes)
    win = next(e for p in planes for evs in p.lines.values() for e in evs
               if e.name == trace_reduce.WINDOW_SPAN)
    lo, hi = win.start_ns, win.end_ns
    spans = host_spans(planes, lo, hi)
    dev = next(p for p in planes if trace_reduce.DEVICE_PLANE.match(p.name))
    busy = trace_reduce.union(
        c for c in (trace_reduce.clip((e.start_ns, e.end_ns), lo, hi)
                    for e in dev.lines.get(trace_reduce.OPS_LINE, []))
        if c)
    idle = attribute(trace_reduce.complement(busy, lo, hi), spans)
    totals = defaultdict(lambda: [0, 0.0])
    for e in spans:
        totals[e.name][0] += 1
        totals[e.name][1] += (e.end_ns - e.start_ns) / 1e9
    by_scope, by_op, prog_s, runs = program_ops(planes, lo, hi, scopes)
    in_scope = sum(v for k, v in by_scope.items() if k)
    return {
        "window_s": red.window_s, "busy_s": red.busy_s,
        "idle_share": 1.0 - red.busy_s / red.window_s,
        "idle_gaps": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "spans": {k: v for k, v in sorted(totals.items())},
        "program_runs": runs, "program_s": prog_s,
        "scope_s": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
        "scope_share": in_scope / max(sum(by_scope.values()), 1e-12),
        "scope_ms_per_run": {k: 1e3 * v / max(runs, 1)
                             for k, v in by_scope.items()},
        "top_ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:12],
        **clock_offsets(planes, spans),
    }


def run(cell, seed: int, seconds: float, windows, out: str):
    """Set up ``cell`` and print one JSON line for the untraced window of
    ``seconds`` and one for each traced window of ``windows``."""
    import jax

    import hostclock
    from repro.core import continuous_batching as cb
    from repro.core import tracing

    now = hostclock.now
    drv = cell.driver()
    config = cell.config
    cfg = drv.pool_config(config)
    seeds = drv.seeds_of(seed)
    data, db, graph = drv.build(config, seeds)
    pool = drv.VectorPool(cfg, db, graph, policy=config["policy"],
                          seed=seeds["pool"])
    eng = pool.replicas[0].engine
    drv.warm_engine(eng, data.rows(64, seeds["warm"]))
    scopes = scope_of(cb.extend_multi.lower(
        eng.state, eng.db, eng.graph, num_steps=eng.extend_chunk,
        p=cfg.parents_per_step, task_batch=cfg.task_batch,
        use_pallas=eng.use_pallas, metric=cfg.metric,
        distance_mode=eng.distance_mode).compile().as_text())
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "scopes.json"), "w") as f:
        json.dump(scopes, f)
    self_s, slow = defaultdict(float), []

    class Loop(drv.Loop):
        def step(self):
            t = now()
            out = super().step()
            took = now() - t
            for k, v in tracing.TRACER.call.items():
                self_s[k] += v
            if took > drv.SLOW_S:
                k = max(tracing.TRACER.call, key=tracing.TRACER.call.get)
                slow.append((round(took * 1e3, 3), k,
                             round(tracing.TRACER.call[k] * 1e3, 3)))
            return out

    loop = Loop(pool)
    rid = [drv.WARM_RID]

    def traffic(seconds, n):
        """Arrivals and query rows of a window, made before it opens."""
        arr = arrivals.open_loop(cell.traffic, seconds,
                                 np.random.default_rng(seeds["traffic"] + n))
        return arr, data.rows(len(arr), seeds["queries"] + n), seconds

    def serve(arr, queries, seconds):
        reqs = loop.serve(arr.kind, queries, arr.due_s, loop.t(), seconds,
                          cfg, rid[0], {}, [])
        rid[0] += len(reqs)

    def queues():
        m = pool.metrics
        return dict(m.queue_wait_s), dict(m.admitted)

    def queue_ms(q0):
        """Mean first-admission queue wait by stage since ``q0``."""
        (w0, n0), (w1, n1) = q0, queues()
        return {k: 1e3 * (w1[k] - w0.get(k, 0.0)) / (n1[k] - n0.get(k, 0))
                for k in n1 if n1[k] > n0.get(k, 0)}

    serve(*traffic(drv.WARM_S, 0))
    window = traffic(seconds, 1)
    # the untraced window: self time per chunk, and the cost of a span
    stats0 = {k: v.count for k, v in tracing.TRACER.stats.items()}
    self_s.clear()
    q0 = queues()
    t0 = now()
    serve(*window)
    wall = now() - t0
    counts = {k: v.count - stats0.get(k, 0)
              for k, v in tracing.TRACER.stats.items()}
    chunks = max(counts.get("dispatch", 0), 1)
    probe, n_probe = tracing.Tracer(), 200_000
    t = time.perf_counter()
    for _ in range(n_probe):
        with probe.span("x"):
            pass
    per_span = (time.perf_counter() - t) / n_probe
    spans_per_chunk = sum(counts.values()) / chunks
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "untraced_s": wall,
        "chunks": chunks, "chunk_ms": 1e3 * wall / chunks,
        "self_ms_per_chunk": {k: 1e3 * v / chunks
                              for k, v in sorted(self_s.items())},
        "span_counts": counts, "spans_per_chunk": spans_per_chunk,
        "span_max_ms": {k: 1e3 * v.max_s
                        for k, v in sorted(tracing.TRACER.stats.items())},
        "span_cost_us": per_span * 1e6,
        "span_cost_share": per_span * spans_per_chunk * chunks / wall,
        "queue_wait_ms": queue_ms(q0), "slow_calls": slow[:10]}),
        flush=True)

    for n, secs in enumerate(windows):
        window = traffic(secs, 2 + n)
        q0 = queues()
        tr = hostclock.WindowTrace(os.path.join(ROOT, ".bench_cache",
                                                "spans"))
        tr.start()
        serve(*window)
        tr.stop()
        row = reduce_window(trace_reduce.load(tr.path), scopes)
        row["queue_wait_ms"] = queue_ms(q0)
        keep = os.path.join(out, f"window{n}_{secs:g}s.xplane.pb.gz")
        with open(tr.path, "rb") as f, gzip.open(keep, "wb") as g:
            shutil.copyfileobj(f, g)
        tr.discard()
        row.update(window=n, traced_seconds=secs, trace=keep)
        print(json.dumps(row), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--windows", default="3")
    ap.add_argument("--out", default=os.path.join(ROOT, ".bench_cache",
                                                  "chunk_spans"))
    args = ap.parse_args()
    configure_jax()
    run(spec.load_cell(args.workload), args.seed, args.seconds,
        [float(w) for w in args.windows.split(",")], args.out)


if __name__ == "__main__":
    main()
