"""Program spans on the profiler's clock.

``span(name)`` marks one stage of the serve loop, ingestion or planning.
It always enters ``jax.profiler.TraceAnnotation(name)``, so a profiled
run finds the stage on the host plane of the same trace as the device
ops.  Inside ``recording()`` it also appends ``(name, t0, t1)`` on
``time.perf_counter`` to the list the recording yields, kept in memory.
With no recording active a span costs one TraceMe.

``counters(**increments)`` marks counter increments on the same clock: a
zero-length TraceMe named ``counters`` whose arguments are the
increments, so a profiled window can sum the counters it holds.

Spans (DESIGN.md §7): ``serve.admit``, ``serve.harvest``, ``pool.scan``,
``pool.upload``, ``pool.run``, ``pool.download``, ``ingest.coalesce``,
``register.plan``.
"""

from __future__ import annotations

import contextlib
import contextvars
import time

import jax

_recording: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "repro_trace_recording", default=None)


@contextlib.contextmanager
def span(name: str):
    rec = _recording.get()
    with jax.profiler.TraceAnnotation(name):
        if rec is None:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            rec.append((name, t0, time.perf_counter()))


def counters(**increments: int) -> None:
    with jax.profiler.TraceAnnotation("counters", **increments):
        pass


@contextlib.contextmanager
def recording():
    """Record every span this thread (or task) enters inside the block;
    yields the list of ``(name, t0, t1)`` they append to."""
    rec: list = []
    token = _recording.set(rec)
    try:
        yield rec
    finally:
        _recording.reset(token)
