"""Host spans of the serving loop, on the profiler's clock.

``Spans.span(name, attrs)`` enters a ``jax.profiler.TraceAnnotation``.
While a profiler session records, the span lands in the profiler's own
trace beside the device planes, on the host clock that also stamps the
runtime's program enqueue (``DoEnqueueProgram``) and completion
(``CompleteCallbacks``) events, and it carries its attributes (the
engine's counters) as TraceMe metadata.  In an iteration that began with
no session recording, a span is a shared object that does nothing.

Attributes are built lazily: ``attrs`` is a zero-argument callable that
returns a dict, called only while the profiler records, which ``begin``
reads once per engine iteration.  ``_Span.set`` adds attributes known
only inside the span (what a commit loop committed).  A list value is
written space-separated: TraceMe splits metadata on ``,``, ``#`` and
``=``, so no value may hold them.

``EngineConfig(trace=True)`` (the per-iteration wall-clock record that
the calibration plane reads) is taken at the same boundaries: with
``wall`` set, every span leaves its ``time.perf_counter`` entry and exit
under its name in ``marks``.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

from jax.profiler import TraceAnnotation

Attrs = Optional[Callable[[], dict]]


def _format(attrs: dict) -> dict:
    """TraceMe metadata of an attribute dict: lists space-separated."""
    return {k: " ".join(map(str, v)) if isinstance(v, (list, tuple)) else v
            for k, v in attrs.items()}


class _Span:
    __slots__ = ("_owner", "_name", "_attrs", "_ta", "_t")

    def __init__(self, owner: "Spans", name: str, attrs: Attrs):
        self._owner, self._name, self._attrs = owner, name, attrs

    def __enter__(self) -> "_Span":
        if self._owner.on and self._attrs is not None:
            self._ta = TraceAnnotation(self._name, **_format(self._attrs()))
        else:
            self._ta = TraceAnnotation(self._name)
        self._ta.__enter__()
        if self._owner.wall:
            self._t = time.perf_counter()
        return self

    def set(self, attrs: Callable[[], dict]) -> None:
        """Attributes known only inside the span."""
        if self._owner.on:
            self._ta.set_metadata(**_format(attrs()))

    def __exit__(self, *exc) -> None:
        if self._owner.wall:
            self._owner.marks[self._name] = (self._t, time.perf_counter())
        self._ta.__exit__(*exc)


class _Off:
    """The span of an iteration that nothing records."""

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, attrs: Callable[[], dict]) -> None:
        return None


_OFF = _Off()


class Spans:
    """One engine's span helper: ``on`` while the profiler records,
    ``it`` the engine iterations begun, and with ``wall`` set the wall
    clock of each span name's latest entry and exit."""

    def __init__(self, wall: bool = False):
        self.wall = wall
        self.on = False
        self.it = 0
        self.marks: dict[str, tuple[float, float]] = {}

    def begin(self) -> None:
        """Start of an engine iteration: read whether the profiler
        records, so this iteration writes its spans and builds their
        attributes, or does neither."""
        self.on = TraceAnnotation.is_enabled()
        self.it += 1

    def span(self, name: str, attrs: Attrs = None):
        if not (self.on or self.wall):
            return _OFF
        return _Span(self, name, attrs)
