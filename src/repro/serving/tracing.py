"""Trace spans and a compile counter for the serving path.

``span(name, stats)`` marks a phase of the engine or the front door on
the JAX profiler's host plane, on the clock the device planes share.
While no profiler records it costs one check and returns a shared no-op
context; ``stats`` (a callable returning the span's keyword values) is
called only while one does. Capture with ``jax.profiler.trace(dir)`` or
``jax.profiler.start_trace`` / ``stop_trace``; docs/serving.md lists the
spans.

``compiles()`` counts the backend compiles of this process, through one
``jax.monitoring`` listener registered at import.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.profiler import TraceAnnotation

_OFF = contextlib.nullcontext()
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def span(name: str, stats=None):
    """A ``TraceAnnotation`` named ``name`` with ``stats()`` as its
    keyword values while the profiler records, else a no-op context."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return TraceAnnotation(name, **(stats() if stats is not None else {}))


_compiles = 0
_compiles_lock = threading.Lock()  # compiles run on any thread


def _on_event(event: str, duration_s: float, **_) -> None:
    global _compiles
    if event == BACKEND_COMPILE_EVENT:
        with _compiles_lock:
            _compiles += 1


def compiles() -> int:
    """Backend compiles in this process since ``repro.serving`` loaded."""
    return _compiles


jax.monitoring.register_event_duration_secs_listener(_on_event)
