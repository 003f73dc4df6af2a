"""Host spans and compile counts of the serving loop, on the profiler's clock.

``span(name)`` is the one way the program records a span. Off (the
default) it returns one shared no-op context object: the cost is a flag
test and no allocation. On, it enters ``jax.profiler.TraceAnnotation(name)``
-- so a profiler running in the process records the span on its
``/host:CPU`` plane, on the device trace's clock -- and accumulates in
memory, per name, the number of spans, their total seconds and their self
seconds (the total less the time covered by child spans, found with a stack
of open spans). While on, backend compiles and persistent-cache loads are
counted under the innermost open span, so a snapshot says which step
compiled.

Nothing is written anywhere: the caller reads ``snapshot()`` when it is
done (``recording()`` brackets a block with ``reset``, ``enable`` and
``disable``). The tracer is per process: replicas behind ``ReplicaRouter``
share it, so their spans add up under one name.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, Iterator, List

NO_SPAN = "no span"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_hits"


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _Noop()


class Tracer:
    """Per-name span totals: ``[n, s, self_s, compiles]``."""

    def __init__(self) -> None:
        self.on = False
        self.open: List[list] = []          # [name, start, child_s] per span
        self.totals: Dict[str, list] = {}
        self._annotation = None             # jax.profiler.TraceAnnotation

    def entry(self, name: str) -> list:
        return self.totals.setdefault(name, [0, 0.0, 0.0, 0])

    def enable(self) -> None:
        if self._annotation is None:
            import jax
            self._annotation = jax.profiler.TraceAnnotation
            jax.monitoring.register_event_listener(self._event)
            jax.monitoring.register_event_duration_secs_listener(
                self._duration)
        self.on = True

    def _compiled(self) -> None:
        if self.on:
            self.entry(self.open[-1][0] if self.open else NO_SPAN)[3] += 1

    def _event(self, event: str, **_kw) -> None:
        if event == _CACHE_LOAD:
            self._compiled()

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == _COMPILE:
            self._compiled()


class _Span:
    __slots__ = ("_tracer", "_name", "_ann", "_frame")

    def __init__(self, tracer: Tracer, name: str) -> None:
        self._tracer, self._name = tracer, name
        self._ann = tracer._annotation(name)

    def __enter__(self):
        self._ann.__enter__()
        self._frame = [self._name, time.perf_counter(), 0.0]
        self._tracer.open.append(self._frame)
        return self

    def __exit__(self, *exc) -> bool:
        end = time.perf_counter()
        t, frame = self._tracer, self._frame
        if t.open and t.open[-1] is frame:       # not dropped by a reset
            t.open.pop()
            d = end - frame[1]
            e = t.entry(frame[0])
            e[0] += 1
            e[1] += d
            e[2] += d - frame[2]
            if t.open:
                t.open[-1][2] += d
        self._ann.__exit__(*exc)
        return False


_TRACER = Tracer()


def span(name: str):
    """Context manager recording one span ``name`` while tracing is on."""
    if not _TRACER.on:
        return _NOOP
    return _Span(_TRACER, name)


def enable() -> None:
    _TRACER.enable()


def disable() -> None:
    _TRACER.on = False


def reset() -> None:
    """Forget every total and every open span."""
    _TRACER.totals.clear()
    _TRACER.open.clear()


@contextmanager
def recording() -> Iterator[None]:
    """Trace the block from a clean slate; read ``snapshot()`` after it."""
    reset()
    enable()
    try:
        yield
    finally:
        disable()


def snapshot() -> Dict[str, dict]:
    """``{name: {"n", "s", "self_s", "compiles"}}`` of the spans so far;
    compiles outside any span appear under ``NO_SPAN``."""
    return {k: {"n": n, "s": s, "self_s": self_s, "compiles": c}
            for k, (n, s, self_s, c) in _TRACER.totals.items()}
