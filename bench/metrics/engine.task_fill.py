"""Continuous-batching engine (``core/continuous_batching.py``): share of
the fixed task array that held real distance tasks over the traced part
of the window
(``PoolMetrics.tasks_emitted / tasks_capacity``), in %."""


def read(run):
    c = run.traced
    if not c.get("tasks_capacity"):
        return None
    return 100.0 * c["tasks_emitted"] / c["tasks_capacity"]
