"""Program spans on the profiler's clock, and the compile counter.

``span(name, recorder)`` marks one per-step region of the trainer or the
data plane (``trainer.step``, ``trainer.bank``, ``trainer.dense_grad``,
``trainer.accuracy``, ``dispatcher.execute``).  It does two things:

* opens a ``jax.profiler.TraceAnnotation(name)``, so that a profiler trace
  shows the region in its host plane, on the same clock as the device's
  operations;
* when its recorder is enabled, records the region's host duration into
  ``recorder.stage_hists[name]`` (exact total and count).

A span measures host time only: it adds no synchronisation, so work the
region enqueued may still run on the device after it closes.

Spans nest per thread.  A span given no recorder takes the recorder of the
innermost open span on its thread, so the trainer names the recorder once,
at ``trainer.step``, and the spans below it inherit it.  With no span open
and no recorder, a span only annotates the trace.

The compile counter: one process-wide ``jax.monitoring`` listener records
each ``/jax/core/compile/backend_compile_duration`` event (a program
compiled or loaded from the persistent cache) into
``stage_hists["compile"]`` of the innermost open span's recorder on the
thread that compiled.  A compile outside every span is not recorded.
"""
from __future__ import annotations

import threading
import time
import jax

#: the JAX event that marks one program compiled or fetched from the
#: persistent cache, in the thread that called the function being compiled
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: ``stage_hists`` key the compile counter records into
COMPILE_HIST = "compile"

_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """Context manager: one program span (see the module docstring).

    ``name`` is a fixed string (no ``#k=v`` arguments), so that a trace
    reduction finds the span by exact name.  ``recorder`` is a
    ``TraceRecorder``; None inherits the innermost open span's."""

    __slots__ = ("name", "recorder", "_annotation", "_t0")

    def __init__(self, name: str, recorder=None):
        self.name = name
        self.recorder = recorder

    def __enter__(self) -> "span":
        stack = _stack()
        if self.recorder is None and stack:
            self.recorder = stack[-1].recorder
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        elapsed = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        _stack().pop()
        if self.recorder is not None:
            self.recorder.record_span(self.name, elapsed)


def _on_duration(event: str, duration: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    stack = getattr(_local, "stack", None)
    if stack and stack[-1].recorder is not None:
        stack[-1].recorder.record_span(COMPILE_HIST, duration)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


__all__ = ["COMPILE_EVENT", "COMPILE_HIST", "span"]
