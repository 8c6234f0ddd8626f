"""The pool driver on the CPU at a test's size: its clock, its failures, its
output check, the control that check must refuse, and the faults of the
timed path it must catch. The look for a chip is skipped; the rest of a
run is driven as ``run.py`` drives it."""
import os

import time

import numpy as np
import pytest

import run as bench_run
import spec
from tools import readings
from repro.core import continuous_batching as cb
from repro.core import trinity_pool

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LIMITS = spec.load_json(os.path.join(spec.BENCH_DIR, "limits",
                                    "pool-sift128-1m.json"))
SEED = 3_000_000_019  # above 2**31


def tiny_cell(rate=150.0):
    traffic = {"kind": "open", "rate_per_s": rate,
               "classes": {"prefill": 1.0, "decode": 5.28}}
    return spec.Cell(
        name="tiny", chips=1, config_name="tiny-pool",
        config=spec.load_json(os.path.join(DATA, "tiny-pool.json")),
        traffic_name="tiny", traffic=traffic, limits=LIMITS,
        end_to_end=[], per_layer=[])


def serve(cell, seconds=1.5, on_setup=None, seed=SEED):
    """Drive one run; returns (served, compared numbers, correct)."""
    def setup_done():
        if on_setup is not None:
            on_setup()

    ctx = bench_run.Context(cell=cell, seed=seed, seconds=seconds,
                            trace=None, trace_seconds=0.0, on_chip=False,
                            log=lambda m: None, setup_done=setup_done)
    drv = cell.driver()
    served = drv.run(ctx)
    nums, info = served.check()
    served.info.update(info)
    _, correct = bench_run.judge(nums, cell.limits["compare"])
    return served, nums, correct


@pytest.fixture
def fresh_jit():
    """Faults patched under a jitted program need it traced anew."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct_and_timed_on_the_host(monkeypatch):
    # no time the pool keeps may reach a reported number
    def no_pool_time(*a, **k):
        raise AssertionError("the benchmark read a pool time")

    monkeypatch.setattr(trinity_pool.PoolMetrics, "latencies", no_pool_time)
    monkeypatch.setattr(trinity_pool.PoolMetrics, "p", no_pool_time)

    class HostOnly(trinity_pool.VectorRequest):
        """t_completed may be written by the pool, never read back."""

        @property
        def t_completed(self):
            raise AssertionError("the benchmark read t_completed")

        @t_completed.setter
        def t_completed(self, value):
            pass

    drv = tiny_cell().driver()
    monkeypatch.setattr(drv, "VectorRequest", HostOnly)
    served, nums, correct = serve(tiny_cell())
    assert correct, nums
    assert served.failed == 0 and nums["unfinished"] == 0
    assert nums["id_mismatch"] == 0.0
    assert nums["dist_err"] < 1e-5
    for k in ("prefill_probe_p95_ms", "decode_probe_p95_ms"):
        assert 0 < served.metrics[k] < 5_000
    assert served.counters["tasks_emitted"] > 0


def test_unfinished_probe_counts_as_failed(monkeypatch):
    """A pool whose own clock runs far ahead of the host's stalls (the
    benchmark never moves a clock back): its probes never finish, and they
    count as failed, with their wait in the tail."""
    drv = tiny_cell().driver()
    monkeypatch.setattr(drv, "DRAIN_S", 0.5)

    def stall():
        monkeypatch.setattr(trinity_pool.roofline_model, "extend_time",
                            lambda cfg: 30.0)

    served, nums, correct = serve(tiny_cell(), seconds=1.0, on_setup=stall)
    assert served.failed > 0
    assert nums["unfinished"] == served.failed
    assert not correct
    assert served.metrics["decode_probe_p95_ms"] > 500


def test_control_fails_the_limits():
    """The reference in the program's place, computed on bfloat16 rows and
    queries, must fail the check that the program passes."""
    cell = tiny_cell()
    drv = cell.driver()
    seeds = drv.seeds_of(SEED)
    data, db, graph = drv.build(cell.config, seeds)
    queries = data.rows(300, seeds["queries"])
    rids = np.arange(300)
    ref = drv.reference(cell.config, db, graph, queries, rids, seeds["pool"])
    ctl = drv.reference(cell.config, db, graph, queries, rids, seeds["pool"],
                        precision="bfloat16")
    nums = drv.numbers(db, queries, ctl[0], ctl[1], ref)
    nums["unfinished"] = 0.0
    _, correct = bench_run.judge(nums, cell.limits["compare"])
    assert not correct, nums
    assert nums["dist_err"] > 10 * cell.limits["compare"]["dist_err"]


def test_readings_judge_program_and_control():
    """The readings tool puts both through the harness's comparison: the
    program's answers come out correct and the control's do not."""
    row = readings.read_seed(tiny_cell(), SEED, 1.0, on_chip=False)
    assert row["correct"] and not row["control_correct"], row


def test_check_compares_a_sample_drawn_from_the_seed(monkeypatch):
    drv = tiny_cell().driver()
    monkeypatch.setattr(drv, "CHECK_MAX", 50)
    served, nums, correct = serve(tiny_cell())
    assert correct, nums
    assert served.info["checked"] == 50 < served.attempted


def test_blocked_call_is_reported(monkeypatch):
    """A run_until call in the window that blocks past SLOW_S is counted,
    and its wait lands in the tail."""
    real = trinity_pool.VectorPool.run_until
    calls = []

    def slow(self, t):
        if calls == [True]:
            calls.append(False)
            time.sleep(0.3)
        return real(self, t)

    monkeypatch.setattr(trinity_pool.VectorPool, "run_until", slow)
    served, _, correct = serve(tiny_cell(), seconds=1.0,
                               on_setup=lambda: calls.append(True))
    assert correct
    assert served.info["slow_run_until_calls"] >= 1
    assert max(served.metrics.values()) > 150


def test_fault_state_unchanged(monkeypatch, fresh_jit):
    """An extend that returns its state unchanged: nothing converges."""
    drv = tiny_cell().driver()
    monkeypatch.setattr(drv, "DRAIN_S", 0.5)

    def fault():
        def frozen(state, db, graph, *, num_steps, **kw):
            import jax.numpy as jnp
            r = state.active.shape[0]
            return (state, jnp.zeros((num_steps, r), bool),
                    jnp.zeros((num_steps,), jnp.int32))
        monkeypatch.setattr(cb, "extend_multi", frozen)

    served, nums, correct = serve(tiny_cell(), seconds=1.0, on_setup=fault)
    assert not correct and served.failed > 0


def test_fault_half_batch_left_out(monkeypatch, fresh_jit):
    """The distance stage leaves out the second half of the task batch."""
    real = cb.kernel_ref.distance_tasks_ref

    def half(db, queries, task_ids, task_slot, metric="l2"):
        d = real(db, queries, task_ids, task_slot, metric=metric)
        n = d.shape[0] // 2
        return d.at[n:].set(1e30)

    monkeypatch.setattr(cb.kernel_ref, "distance_tasks_ref", half)
    _, nums, correct = serve(tiny_cell())
    assert not correct, nums


def test_fault_answer_altered(monkeypatch):
    """Each answer's nearest id replaced where the engine produces it."""
    real = cb.ContinuousBatchingEngine.step_multi

    def altered(self, num_steps=None):
        out, tasks = real(self, num_steps)
        bad = []
        for rid, ids, dists, ext, sub in out:
            ids = ids.copy()
            ids[0] = (ids[0] + 1) % self.corpus_n
            bad.append((rid, ids, dists, ext, sub))
        return bad, tasks

    monkeypatch.setattr(cb.ContinuousBatchingEngine, "step_multi", altered)
    _, nums, correct = serve(tiny_cell())
    assert not correct, nums
