"""Host spans of the served path, on the profiler's clock.

``span(name, **ids)`` opens a ``jax.profiler.TraceAnnotation`` when tracing
is on, so the span lands on the profiler's host plane, which shares its
origin with the device planes: a device op or an idle gap of the device can
be named by the host span it falls in. When tracing is off (the default)
``span`` returns one shared no-op context and records nothing.

Spans inside a tick carry ``tick``: ``tick_span`` opens the root
``relserve.tick`` span and every span opened under it gets that id, so the
scheduler and the executor need not know the engine's tick count. Every
span name starts with ``relserve.``. An annotation made while no profiler
trace is active records nothing, so turn tracing on and start the profiler
(``jax.profiler.start_trace``) to see the spans.
"""
from __future__ import annotations

import functools
from typing import Optional

PREFIX = "relserve."


class _Off:
    """The shared no-op span of tracing off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **ids) -> None:
        pass


OFF = _Off()
_on = False
_tick: Optional[int] = None      # id of the open tick span, if any
_Annotation = None               # jax.profiler.TraceAnnotation, once enabled


def enable() -> None:
    global _on, _Annotation
    from jax.profiler import TraceAnnotation
    _Annotation, _on = TraceAnnotation, True


def disable() -> None:
    global _on, _tick
    _on, _tick = False, None


def enabled() -> bool:
    return _on


def span(name: str, **ids):
    """A profiler span named ``name`` with ``ids`` as its arguments (and the
    open tick's id); the shared no-op ``OFF`` when tracing is off."""
    if not _on:
        return OFF
    if _tick is not None:
        ids.setdefault("tick", _tick)
    return _Annotation(name, **ids)


def traced(name: str):
    """Decorator: run the method inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


class _TickSpan:
    """The root span of one engine tick: spans opened inside it carry its
    id. ``set_metadata`` adds arguments known only once the batch is chosen."""

    __slots__ = ("tick", "_ann", "_outer")

    def __init__(self, tick: int):
        self.tick = tick
        self._ann = _Annotation(PREFIX + "tick", tick=tick)

    def __enter__(self):
        global _tick
        self._outer, _tick = _tick, self.tick
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        global _tick
        self._ann.__exit__(*exc)
        _tick = self._outer
        return False

    def set_metadata(self, **ids) -> None:
        self._ann.set_metadata(**ids)


def tick_span(tick: int):
    """The ``relserve.tick`` span of tick ``tick``; ``OFF`` when off."""
    if not _on:
        return OFF
    return _TickSpan(tick)
