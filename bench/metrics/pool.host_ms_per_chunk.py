"""The pool and its scheduler (``core/trinity_pool.py``,
``core/scheduler.py``): host milliseconds per fused chunk outside the
host's wait for the device, over the traced part of the window:
(seconds in ``trinity.run_until`` - seconds in ``trinity.sync``) over the
number of ``trinity.dispatch`` spans. The program's tracer
(``repro.core.tracing``) keeps the totals of the spans a profiler session
recorded; a program without it reads nothing."""


def read(run):
    try:
        from repro.core.tracing import TRACER
    except ImportError:
        return None
    rec = TRACER.recorded
    chunks = rec["dispatch"].count if "dispatch" in rec else 0
    if run.trace is None or not chunks or "run_until" not in rec:
        return None
    sync = rec["sync"].total_s if "sync" in rec else 0.0
    return 1e3 * (rec["run_until"].total_s - sync) / chunks
