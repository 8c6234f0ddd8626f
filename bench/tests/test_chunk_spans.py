"""The span-level breakdown of a chunk (``tools/chunk_spans.py``: each idle
gap to the innermost host span covering it, each operation of the extend
program to its named scope), the readers of the pool's span metrics, and
the four older metrics on the older recorded trace: by hand on made-up
traces, and on traces recorded on a TPU v5e chip."""
import gzip
import json
import os
import sys

import pytest

import run as bench_run
import spec
import trace_reduce as tr
import work
from tools import chunk_spans as cs
from trace_reduce import Event, Plane
from repro.core import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# 0.2 s of the spanned, scoped program serving sift1m-steady (10^6 rows,
# 200 probes/s) on one TPU v5e chip, traced by tools/chunk_spans.py, with
# the scope of each instruction of the extend program it ran
SPANNED = os.path.join(DATA, "pool_spans_v5e.xplane.pb.gz")
SCOPES = os.path.join(DATA, "pool_spans_v5e.scopes.json")
OLD = os.path.join(DATA, "pool_v5e.xplane.pb.gz")

NESTED = [Event("bench.run_until", 0, 100), Event("trinity.run_until", 5, 95),
          Event("trinity.schedule", 10, 40), Event("trinity.admit", 20, 30),
          Event("trinity.dispatch", 40, 45), Event("trinity.sync", 45, 80),
          Event("bench.submit", 120, 130)]


def _load(path, tmp_path):
    out = tmp_path / os.path.basename(path)[:-3]
    with gzip.open(path, "rb") as f:
        out.write_bytes(f.read())
    return tr.load(str(out))


def test_innermost_pieces():
    assert cs.innermost(NESTED) == [
        (0, 5, "bench.run_until"), (5, 10, "trinity.run_until"),
        (10, 20, "trinity.schedule"), (20, 30, "trinity.admit"),
        (30, 40, "trinity.schedule"), (40, 45, "trinity.dispatch"),
        (45, 80, "trinity.sync"), (80, 95, "trinity.run_until"),
        (95, 100, "bench.run_until"), (120, 130, "bench.submit")]


def test_attribute_gaps_to_the_innermost_span():
    gaps = [(0, 25), (50, 110), (125, 140)]
    got = cs.attribute(gaps, NESTED)
    assert got == pytest.approx({
        "run_until": 10e-9, "trinity.run_until": 20e-9,
        "trinity.schedule": 10e-9, "trinity.admit": 5e-9,
        "trinity.sync": 30e-9, "submit": 5e-9, "other": 20e-9})
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in gaps) / 1e9)


HLO = """\
  %fusion.70 = s32[8192]{0} fusion(%a), kind=kCustom, metadata={op_name="jit(extend_multi)/while/body/closed_call/build_tasks/vmap()/gather" stack_frame_id=10}
  %distance_tasks.6 = f32[1,2048]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(extend_multi)/while/body/closed_call/distance/jit(distance_tasks)/distance_tasks/pallas_call"}
  ROOT %tuple.3 = (s32[]) tuple(%p), metadata={op_name="jit(extend_multi)/while"}
  %copy.1 = s32[64]{0} copy(%q)
"""


def _device():
    return Plane("/device:TPU:0", {
        "XLA Modules": [Event("jit_extend_multi(1)", 100, 200),
                        Event("jit_admit_many(2)", 210, 230)],
        "XLA Ops": [
            Event("%while.2 = (s32[]) while(%t)", 100, 200),
            Event("%fusion.70 = s32[8192]{0:T(1024)} fusion(%a)", 100, 140),
            Event("%distance_tasks.6 = f32[1,2048]{1,0} custom-call(%x)",
                  140, 180),
            Event("%copy.1 = s32[64]{0} copy(%q)", 180, 190),
            Event("%fusion.70 = s32[64]{0} fusion(%b)", 210, 220)]})


def test_scopes_of_the_extend_program():
    scopes = cs.scope_of(HLO)
    assert scopes == {"fusion.70": "build_tasks",
                      "distance_tasks.6": "distance", "tuple.3": ""}
    by_scope, by_op, prog_s, runs = cs.program_ops([_device()], 0, 1000,
                                                   scopes)
    # control flow left out; the admission program's fusion.70 is not
    # the extend program's
    assert by_scope == pytest.approx({"build_tasks": 40e-9,
                                      "distance": 40e-9, "": 10e-9})
    assert by_op == pytest.approx({
        "build_tasks/fusion.70 s32[8192]": 40e-9,
        "distance/distance_tasks.6 f32[1,2048]": 40e-9,
        "/copy.1 s32[64]": 10e-9})
    assert (prog_s, runs) == (pytest.approx(100e-9), 1)


def test_reduce_window_by_hand():
    host = Plane("/host:CPU", {"python": [Event("bench.window", 90, 240)]
                               + [Event(e.name, e.start_ns + 90,
                                        e.end_ns + 90) for e in NESTED]})
    row = cs.reduce_window([host, _device()], cs.scope_of(HLO))
    assert row["window_s"] == pytest.approx(150e-9)
    # busy [100, 200) and [210, 220): idle [90, 100) inside the calls,
    # [200, 210) and [220, 240) under no span
    assert row["idle_gaps"] == pytest.approx({
        "run_until": 5e-9, "trinity.run_until": 5e-9, "other": 30e-9})
    assert row["spans"]["trinity.sync"] == [1, pytest.approx(35e-9)]
    assert row["scope_share"] == pytest.approx(80 / 90)
    assert row["program_runs"] == 1
    # the execution starts 30 ns before its dispatch span: a clock offset;
    # no sync span ends after it
    assert row["dispatch_to_program_ms"] == pytest.approx([-3e-5] * 3)
    assert row["program_end_to_sync_end_ms"] is None


class _Trace:
    window_s = 3.0


def _run(trace=_Trace()):
    return bench_run.Run(counters={}, traced={}, trace=trace, peak={})


def test_span_metric_readers(monkeypatch):
    t = tracing.Tracer()
    monkeypatch.setattr(tracing, "TRACER", t)
    host = spec.metric_reader("pool.host_ms_per_chunk")
    sync = spec.metric_reader("engine.sync_ms_per_chunk")
    assert host.read(_run()) is None and sync.read(_run()) is None
    for _ in range(4):
        t.recorded["dispatch"].add(0.001)
        t.recorded["sync"].add(0.002)
    t.recorded["run_until"].add(0.030)
    t.recorded["admit"].add(0.005)
    assert host.read(_run()) == pytest.approx(1e3 * (0.030 - 0.008) / 4)
    assert sync.read(_run()) == pytest.approx(1e3 * 0.008 / 4)
    # an untraced run, and a program without the tracer, read nothing
    assert host.read(_run(None)) is None and sync.read(_run(None)) is None
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert host.read(_run()) is None and sync.read(_run()) is None


def test_older_metrics_read_as_before_on_the_older_trace(tmp_path):
    """The four metrics of the first benchmark read what they read before
    the program had spans and scopes, on the same inputs."""
    red = tr.reduce(_load(OLD, tmp_path))
    c = {"tasks_emitted": 40000, "tasks_capacity": 163840, "dim": 128}
    run = bench_run.Run(counters=c, traced=c, trace=red,
                        peak=work.peaks("TPU v5 lite"))
    got = {m: spec.metric_reader(m).read(run)
           for m in ("engine.task_fill", "extend_mfu",
                     "distance_kernel_roofline", "device_idle.pool")}
    assert got == pytest.approx({
        "engine.task_fill": 24.4140625, "extend_mfu": 0.10695065571735807,
        "distance_kernel_roofline": 0.5855240637556135,
        "device_idle.pool": 87.74149577757048}, rel=1e-12)


def test_recorded_spanned_v5e_trace(tmp_path):
    planes = _load(SPANNED, tmp_path)
    with open(SCOPES) as f:
        scopes = json.load(f)
    red = tr.reduce(planes)
    # the first benchmark's reduction still finds the kernel by its name,
    # inside the extend program
    assert 0 < red.ops("distance_tasks") < red.program("jit_extend_multi")
    row = cs.reduce_window(planes, scopes)
    win = next(e for p in planes for es in p.lines.values() for e in es
               if e.name == tr.WINDOW_SPAN)
    spans = cs.host_spans(planes, win.start_ns, win.end_ns)
    # every program span nests in one of the driver's run_until calls
    calls = [e for e in spans if e.name == "bench.run_until"]
    for e in spans:
        if e.name.startswith("trinity."):
            assert any(c.start_ns <= e.start_ns and e.end_ns <= c.end_ns
                       for c in calls), e
    # one dispatch and one wait per extend program the device ran
    assert row["spans"]["trinity.dispatch"][0] == row["program_runs"] > 0
    assert row["spans"]["trinity.sync"][0] == row["program_runs"]
    # all idle time attributed; the program's spans, not the run_until
    # calls' own code, hold the idle time inside the calls
    idle = row["idle_gaps"]
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s,
                                               rel=1e-6)
    inside = sum(v for k, v in idle.items()
                 if k == "run_until" or k.startswith("trinity."))
    assert idle["run_until"] + idle["trinity.run_until"] <= 0.1 * inside
    assert max(idle, key=idle.get).startswith("trinity.")
    # the five scopes hold at least 90% of the extend program's time
    assert row["scope_share"] >= 0.9
    assert max(row["scope_s"], key=row["scope_s"].get) == "build_tasks"
    assert row["top_ops"][0][0].startswith("build_tasks/fusion.")
