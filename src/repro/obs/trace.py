"""Host-side phase tracing (DESIGN.md §10).

:func:`span` times a named phase with ``time.perf_counter`` and records
the duration into a registry histogram of the same name — the phases the
system cares about are enumerated in the §10 namespace table
(``train/phase/step``, ``serve/phase/decode``, ``genfit/phase/fit``, …).
Spans nest: a thread-local stack tracks the open spans, so instrumented
code can ask :func:`current_spans` where it is (and tests pin that
nesting is restored even when the body raises).

Device alignment: with ``registry.annotate`` set (the launchers flip it
on together with ``--profile-dir``), every span additionally opens a
``jax.profiler.TraceAnnotation``, so the host phase boundaries appear as
named regions on the TraceMe timeline of a ``jax.profiler.trace``
capture and device activity can be attributed to the host phase that
launched it. :class:`ProfileWindow` drives that capture for a bounded
step window — profiling a 100k-step run must not write 100k steps of
trace.

Disabled fast path: when the registry is off (and not annotating),
``span()`` returns a shared no-op context manager — no Span object, no
clock read, nothing on the stack.
"""
from __future__ import annotations

import threading
import time
from typing import Optional, Tuple

from repro.obs.registry import NULL_REGISTRY, Registry

_tls = threading.local()


def _stack(create: bool = True):
    s = getattr(_tls, "spans", None)
    if s is None and create:
        s = _tls.spans = []
    return s


def current_spans() -> Tuple[str, ...]:
    """Names of the open spans on this thread, outermost first."""
    return tuple(_stack())


def _trace_annotation(name: str):
    try:
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name)
    except Exception:       # profiler unavailable: annotation is best-effort
        return None


class Span:
    """Timed phase: records seconds into ``registry.histogram(name)``."""

    __slots__ = ("name", "_hist", "_annotation", "t0", "seconds")

    def __init__(self, name: str, registry: Registry):
        self.name = name
        self._hist = registry.histogram(name)
        self._annotation = (_trace_annotation(name) if registry.annotate
                            else None)
        self.t0 = 0.0
        self.seconds: Optional[float] = None

    def __enter__(self) -> "Span":
        _stack().append(self.name)
        if self._annotation is not None:
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.seconds = time.perf_counter() - self.t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        self._hist.observe(self.seconds)
        popped = _stack().pop()
        assert popped == self.name, f"span stack corrupt: {popped} != " \
                                    f"{self.name}"


class _NullSpan:
    __slots__ = ()
    name = "<null>"
    seconds = None

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


def span(name: str, registry: Optional[Registry] = None):
    """Context manager timing ``name`` into ``registry``. With a None or
    disabled registry this returns a shared no-op singleton (zero
    allocation — the train loop wraps every step unconditionally)."""
    if registry is None or not registry.enabled:
        return _NULL_SPAN
    return Span(name, registry)


class ProfileWindow:
    """Bounded ``jax.profiler`` capture driven by the training loop.

    ``tick(step)`` starts the device+host trace the first time it is
    called (the loop calls it only from steady-state steps, so XLA
    compilation never pollutes the capture) and stops it after
    ``n_steps`` ticks. Inert when ``profile_dir`` is falsy; when a
    directory was asked for and the trace cannot start, ``tick`` raises
    (a run that was meant to be profiled must not finish unprofiled).
    ``stop()`` is idempotent and always safe to call at loop
    exit/preemption.
    """

    def __init__(self, profile_dir: Optional[str], n_steps: int = 5):
        self.profile_dir = profile_dir
        self.n_steps = n_steps
        self._ticks = 0
        self._running = False

    def tick(self, step: int) -> None:
        if not self.profile_dir:
            return
        if not self._running and self._ticks == 0:
            import jax.profiler
            jax.profiler.start_trace(self.profile_dir)
            self._running = True
        self._ticks += 1
        if self._ticks >= self.n_steps:
            self.stop()

    def stop(self) -> None:
        if self._running:
            import jax.profiler
            jax.profiler.stop_trace()
            self._running = False
            self.profile_dir = None         # one window per run
