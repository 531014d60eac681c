"""Host spans of the serving program, on the profiler's clock.

Every span is a `jax.profiler.TraceAnnotation`, so a trace taken with
`jax.profiler.trace(dir)` around a serving loop holds the program's spans
in the same ``.xplane.pb`` as the device's operations, on one clock.  With
no profiler running an annotation is a no-op (about a microsecond), and
its keyword metadata (``step=``, ``slots=``, ``rows=``, ``rid=``) is only
encoded while a trace is active.  There is no recorder of its own: the
profiler's buffer holds the spans until the trace stops.

The spans, nested as listed (docs/serving.md, Observability):

  engine.step        one `ServingEngine.step` (a step annotation, root)
  engine.admit       deadlines, admission, page allocation, prefix match
  engine.prefill     job packing, the prefill dispatch, first-token emit
  engine.pages       append-page allocation
  engine.spec        one speculative round
  engine.emit        emit, host sampling and retire after a decode step
  backend.decode     a backend's whole `decode_step`
  backend.prefill    a backend's whole prefill call
  backend.upload     the rebuild of the decode step's device mirrors
  backend.download   the blocking read of a dispatch's result
  supervisor.backoff the supervisor's retry sleep
  host.gc            a Python garbage collection (any thread)
"""

from __future__ import annotations

import gc

import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation


def span(name: str, **meta) -> TraceAnnotation:
    """A host span ``name`` with keyword metadata."""
    return TraceAnnotation(name, **meta)


def step_span(step: int) -> StepTraceAnnotation:
    """The root span of one engine step, numbered by the engine."""
    return StepTraceAnnotation("engine.step", step_num=step)


def download(x) -> np.ndarray:
    """Block on a dispatch's result and bring it to the host."""
    with TraceAnnotation("backend.download"):
        return np.asarray(x)


_gc_span = None


def _gc_hook(phase: str, info: dict) -> None:
    """`gc.callbacks` hook: a ``host.gc`` span around each collection
    that starts while a trace is active."""
    global _gc_span
    if phase == "start":
        if TraceAnnotation.is_enabled():
            _gc_span = TraceAnnotation("host.gc",
                                       generation=info["generation"])
            _gc_span.__enter__()
    elif _gc_span is not None:
        sp, _gc_span = _gc_span, None
        sp.__exit__(None, None, None)


gc.callbacks.append(_gc_hook)
