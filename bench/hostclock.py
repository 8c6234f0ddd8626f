"""The host clock, the window's trace and host spans.

Every time the benchmark reports is read here, with ``time.perf_counter``;
none comes from the program. Host spans are ``jax.profiler``
annotations named ``bench.<what>``, so they land in the same trace as the
device's operations and ``trace_reduce`` can attribute idle gaps to them.
"""
from __future__ import annotations

import gc
import glob
import os
import shutil
import time

import jax

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def now() -> float:
    return time.perf_counter()


def span(name: str):
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class CompileCount:
    """Host times at which JAX traced, lowered or compiled a program, or
    loaded one from its persistent cache; the window should see none."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.events = []  # (host time at the end, event, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs):
        if event in self.EVENTS:
            self.events.append((now(), event, duration))

    def since(self, t: float):
        """The events that ended at or after host time ``t``."""
        return [e for e in self.events if e[0] >= t]


class GcPauses:
    """Pauses of Python's cyclic garbage collector while registered."""

    def __init__(self):
        self.pauses = []  # (generation, seconds)
        self._t = None

    def _on_gc(self, phase: str, info: dict):
        if phase == "start":
            self._t = now()
        elif self._t is not None:
            self.pauses.append((info["generation"], now() - self._t))
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)


class WindowTrace:
    """A profiler trace of part of the window. ``start``/``stop`` bracket
    it with the ``bench.window`` span, whose extent is the traced window."""

    def __init__(self, directory: str):
        self.directory = directory
        self._span = None
        self.path = None

    def start(self):
        shutil.rmtree(self.directory, ignore_errors=True)
        # no Python function tracing: it slows the host several times over
        # and fills the trace; the bench.* spans are host trace events
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    @property
    def active(self) -> bool:
        return self._span is not None

    def stop(self):
        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.directory}")
        self.path = found[-1]

    def discard(self):
        shutil.rmtree(self.directory, ignore_errors=True)
