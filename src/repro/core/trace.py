"""Program spans and counters on the profiler's own clock.

``span(name, **attrs)`` marks one interval of host work and
``count(name, n)`` adds to a counter.  Both record only while a JAX
profiler session records (``jax.profiler.trace`` / ``start_trace``), the
test the profiler itself makes for every ``TraceMe``.  While recording,
a span

- opens a ``TraceMe("repro." + name, **attrs)`` (what
  ``jax.profiler.TraceAnnotation`` is), so
  it lands in the ``.xplane.pb`` as a host event on the timeline of the
  device's ``XLA Ops``, with ``attrs`` as the event's stats (a ``#`` in
  a string reads ``~`` there);
- adds to a process-wide table per name: total seconds, self seconds
  (the duration less that of the spans nested in it on the same thread)
  and the number of spans.

While not recording, ``span`` hands back one shared no-op context and
``count`` does nothing: a counted quantity may be passed as a zero-arg
callable, which is only called while recording.  ``timed`` is ``span``
for a caller that needs the interval's own ``perf_counter`` readings
(``t0``, ``t1``) whether or not the profiler records.

``spanned(name)`` makes each call of a function one span.
``summary()`` returns the table; ``reset()`` clears it.  The module
imports jaxlib's profiler binding alone, not JAX.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Callable, Dict, List, Union

from jaxlib._profiler import TraceMe

__all__ = ["recording", "span", "spanned", "timed", "count", "summary",
           "reset"]

#: True only while a profiler session records
recording: Callable[[], bool] = TraceMe.is_enabled

_lock = threading.Lock()
_spans: Dict[str, List[float]] = {}      # name -> [total_s, self_s, n]
_counters: Dict[str, float] = {}
_local = threading.local()               # .stack: the open spans


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


class Span:
    """One interval of host work; ``t0``/``t1`` are its ``perf_counter``
    readings, taken inside the profiler annotation."""

    __slots__ = ("name", "attrs", "on", "t0", "t1", "_ann", "_child_s")

    def __init__(self, name: str, attrs: Dict, on: bool):
        self.name, self.attrs, self.on = name, attrs, on
        self._ann = None
        self._child_s = 0.0

    def __enter__(self) -> "Span":
        if self.on:
            # the profiler keeps stats in the event's name between '#'s
            self._ann = TraceMe("repro." + self.name, **{
                k: v.replace("#", "~") if isinstance(v, str) else v
                for k, v in self.attrs.items()})
            self._ann.__enter__()
            stack = getattr(_local, "stack", None)
            if stack is None:
                stack = _local.stack = []
            stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        if self.on:
            self._ann.__exit__(*exc)
            stack = _local.stack
            stack.pop()
            dur = self.t1 - self.t0
            if stack:
                stack[-1]._child_s += dur
            with _lock:
                row = _spans.setdefault(self.name, [0.0, 0.0, 0])
                row[0] += dur
                row[1] += dur - self._child_s
                row[2] += 1
        return False


def span(name: str, **attrs):
    """A context that records ``name`` while the profiler records."""
    return Span(name, attrs, True) if recording() else _NOOP


def spanned(name: str) -> Callable:
    """Decorator: each call of the function is one ``span(name)``."""
    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return call
    return wrap


def timed(name: str, **attrs) -> Span:
    """``span`` that always takes its ``t0``/``t1`` readings."""
    return Span(name, attrs, recording())


def count(name: str, n: Union[float, Callable[[], float]]) -> None:
    """Add ``n`` (or ``n()``, called only while recording) to ``name``."""
    if not recording():
        return
    if callable(n):
        n = n()
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def summary() -> Dict[str, Dict]:
    """``{"spans": {name: {"total_s", "self_s", "n"}}, "counters":
    {name: value}}`` of everything recorded since the last ``reset``."""
    with _lock:
        return {"spans": {k: {"total_s": t, "self_s": s, "n": int(n)}
                          for k, (t, s, n) in _spans.items()},
                "counters": dict(_counters)}


def reset() -> None:
    with _lock:
        _spans.clear()
        _counters.clear()
