"""Continuous-batching engine (``core/continuous_batching.py``): the
host's wait for the device per fused chunk, in ms, over the traced part
of the window: seconds in ``trinity.sync`` (the ``device_get`` of the
chunk's completion masks and task counts) over the number of
``trinity.dispatch`` spans. The program's tracer (``repro.core.tracing``)
keeps the totals of the spans a profiler session recorded; a program
without it reads nothing."""


def read(run):
    try:
        from repro.core.tracing import TRACER
    except ImportError:
        return None
    rec = TRACER.recorded
    chunks = rec["dispatch"].count if "dispatch" in rec else 0
    if run.trace is None or not chunks:
        return None
    return 1e3 * (rec["sync"].total_s if "sync" in rec else 0.0) / chunks
