"""The reduction from a trace to busy time, program and kernel time, and
idle gaps attributed to host spans: by hand on a small made-up trace, and
on a small trace recorded on a TPU v5e chip."""
import gzip
import os

import pytest

import trace_reduce as tr
from trace_reduce import Event, Plane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# 0.25 s of the pool serving 200 probes/s on one TPU v5e chip (the pool
# driver's loop, seed 301), traced with hostclock.WindowTrace, gzipped
RECORDED = os.path.join(DATA, "pool_v5e.xplane.pb.gz")


def test_union_complement_length():
    u = tr.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 10)])
    assert u == [(0, 3), (5, 9)]
    assert tr.length(u) == 7
    assert tr.complement(u, 0, 12) == [(3, 5), (9, 12)]
    assert tr.complement(u, 1, 6) == [(3, 5)]


def test_attribute_gaps_to_spans():
    spans = [Event("bench.run_until", 0, 4), Event("bench.submit", 6, 8)]
    got = tr.attribute([(2, 7), (9, 10)], spans)
    assert got == pytest.approx({"run_until": 2e-9, "submit": 1e-9,
                                 "other": 3e-9})


def _made_up():
    host = Plane("/host:CPU", {"python": [
        Event("bench.window", 100, 200),
        Event("bench.run_until", 100, 150),
        Event("bench.wait_arrival", 150, 200),
        Event("PjitFunction(extend_multi)", 101, 110)]})
    dev0 = Plane("/device:TPU:0", {
        "XLA Modules": [Event("jit_extend_multi(3)", 90, 130),
                        Event("jit_extend_multi(7)", 160, 170)],
        "XLA Ops": [Event("fusion.1", 90, 110), Event("distance_kernel",
                                                     110, 125),
                    Event("fusion.1", 120, 130),
                    Event("distance_kernel", 160, 170)]})
    dev1 = Plane("/device:TPU:1", {"XLA Ops": [Event("fusion.1", 100, 200)]})
    return [host, dev0, dev1]


def test_reduce_by_hand():
    r = tr.reduce(_made_up())
    assert r.window_s == pytest.approx(100e-9)
    # device 0: busy [100, 130) and [160, 170) inside the window = 40 ns;
    # device 1: 100 ns; the mean over the two
    assert r.busy_s == pytest.approx(70e-9)
    assert r.devices == 2
    assert r.program("jit_extend_multi") == pytest.approx(40e-9)
    assert r.module_calls == {"jit_extend_multi": 2}
    assert r.ops("distance_kernel") == pytest.approx(25e-9)
    # device 0 idle: [130, 150) under run_until, [150, 160) and [170, 200)
    # under wait_arrival
    assert r.idle_by_span == pytest.approx({"run_until": 20e-9,
                                            "wait_arrival": 40e-9})
    b = r.breakdown()
    assert b["idle_gaps"][0][0] == "wait_arrival"
    assert len(b["device_ops"]) <= 10
    # summed over both devices: 20 ns on device 0, 100 ns on device 1
    assert dict(b["device_ops"]) == pytest.approx(
        {"fusion.1": 120e-9, "distance_kernel": 25e-9})


def test_op_names():
    ev = ("%distance_tasks.6 = f32[1,2048]{1,0:T(1,128)S(1)} custom-call("
          "s32[2048]{0:T(1024)S(1)} %and_select_fusion.6)")
    assert tr.op_name(ev) == ("distance_tasks", "custom-call")
    assert tr.op_detail(ev) == "distance_tasks.6 f32[1,2048]"


def test_reduce_needs_one_window_and_a_device():
    planes = _made_up()
    with pytest.raises(ValueError):
        tr.reduce(planes[1:])
    with pytest.raises(ValueError):
        tr.reduce(planes[:1])


def test_recorded_v5e_trace(tmp_path):
    path = tmp_path / "pool.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    planes = tr.load(str(path))
    r = tr.reduce(planes)
    assert 0 < r.busy_s < r.window_s
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    win = next(e for p in planes for es in p.lines.values() for e in es
               if e.name == tr.WINDOW_SPAN)
    # busy is a union: never more than the summed operation time
    assert r.busy_s * 1e9 <= sum(
        min(e.end_ns, win.end_ns) - max(e.start_ns, win.start_ns)
        for e in dev.lines[tr.OPS_LINE]
        if e.end_ns > win.start_ns and e.start_ns < win.end_ns)
    # brute force over device 0's operations, at 100 ns resolution
    step = 100.0
    n = int((win.end_ns - win.start_ns) // step)
    hit = bytearray(n)
    for e in dev.lines[tr.OPS_LINE]:
        a = max(0, int((e.start_ns - win.start_ns) // step))
        b = min(n, int((e.end_ns - win.start_ns) // step))
        for i in range(a, b):
            hit[i] = 1
    assert r.busy_s == pytest.approx(sum(hit) * step / 1e9, rel=0.02)
    assert r.program("jit_extend_multi") > 0
    assert r.module_calls["jit_extend_multi"] == 20
    # the Pallas kernel is found by name, inside the extend program
    assert 0 < r.ops("distance_tasks") < r.program("jit_extend_multi")
    assert "while" not in r.op_s
    top = r.breakdown()["device_ops"]
    assert any(name.startswith("distance_tasks.") for name, _ in top)
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    assert max(r.idle_by_span, key=r.idle_by_span.get) == "run_until"
