"""Host spans and counters of a fleet run, on the JAX profiler's clock.

A run records what its host does as ``repro.*`` spans
(:func:`jax.profiler.TraceAnnotation`) and what its device programs do
under ``aif.*`` scopes (:func:`jax.named_scope`, in the op metadata), so a
profiler trace of ``repro.api.run`` lines each host step up with the device
work it waited for.  Outside a trace a span costs one inactive annotation.

Counters are process-wide host integers: ``runs``, ``launches``,
``tape_bytes`` (slot tape the megakernel's launches read from HBM) and
``folded_slots`` (slot rows their prior folds cover), both counted from
shapes, ``cell_windows``, ``watchdog_events``, and, from a
:mod:`jax.monitoring` listener, ``traces`` (a jit cache miss that traced a
function) and ``compiles`` (a backend compile or persistent-cache load).
None of them waits for the device.  :func:`counters` returns a snapshot.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import threading

import jax

PREFIX = "repro."
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: counters whose change over a call the call's ``repro.run.counts`` marker
#: carries
CALL_COUNTERS = ("traces", "compiles", "launches", "tape_bytes",
                 "folded_slots", "cell_windows", "watchdog_events")

_lock = threading.Lock()
_counts: collections.Counter = collections.Counter()
_run_id: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "repro_run_id", default=None)


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counts[name] += int(n)


def counters() -> dict[str, int]:
    """A snapshot of every counter."""
    with _lock:
        return dict(_counts)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A ``repro.<name>`` host span; inside :func:`run_span` it also
    carries the call's ``run`` id."""
    run = _run_id.get()
    if run is not None:
        args = {"run": run, **args}
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


@contextlib.contextmanager
def run_span(**args):
    """The ``repro.run`` span of one call: takes the next ``runs`` id, tags
    every span opened inside with it, and ends with a zero-length
    ``repro.run.counts`` marker holding the call's counter deltas.  Yields
    the span, whose metadata can be extended once known."""
    with _lock:
        _counts["runs"] += 1
        run = _counts["runs"]
        before = {k: _counts[k] for k in CALL_COUNTERS}
    token = _run_id.set(run)
    try:
        with span("run", **args) as sp:
            yield sp
            now = counters()
            with span("run.counts", **{k: now.get(k, 0) - before[k]
                                       for k in CALL_COUNTERS}):
                pass
    finally:
        _run_id.reset(token)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _TRACE_EVENT:
        count("traces")
    elif event == _COMPILE_EVENT:
        count("compiles")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
