"""Spans of the serving path, on while the JAX profiler traces.

Spans mark where the serving path spends its host time: a micro-batch's
collect, assemble, dispatch and scatter, a fleet round's hold and
finalize, each program call and the sync that forces its outputs.  They
are on exactly while the JAX profiler is tracing (``jax.profiler.
start_trace``, or a capture through ``jax.profiler.start_server``), and
off otherwise; there is no other switch.

* Off, :func:`span` returns one shared no-op context and records nothing:
  a span site costs one ``TraceAnnotation.is_enabled()`` check.
* On, a span opens a ``jax.profiler.TraceAnnotation``, so it lands in the
  profiler's trace on the device trace's clock, and on exit appends one
  flat tuple to a bounded in-memory ring::

      (name, start, end, thread, id, parent, (key, value), ...)

  ``start`` and ``end`` are ``time.perf_counter()`` seconds; ``thread`` is
  ``threading.get_ident()``; ``id`` is the span's own number and
  ``parent`` that of the span it opened inside on the same thread (0 at
  the top); the pairs are the span's ints.  When the ring is full the
  oldest record is dropped, and counted (:func:`dropped`).

:func:`interval` records an interval measured elsewhere, such as a
request's wait in a queue that another thread drains; it goes to the ring
only, as a profiler annotation cannot cross threads.  :func:`collected`
returns the records that start inside a window.

Every name starts with :data:`PREFIX`.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import List

from jax.profiler import TraceAnnotation

__all__ = ["PREFIX", "CAPACITY", "span", "interval", "enabled", "new_id",
           "collected", "dropped", "clear"]

PREFIX = "repro."
# Records kept: a 10 s window of single-row traffic at about 13k
# requests/s records about 160k (one per request, a few per dispatch).
CAPACITY = 1 << 18

enabled = TraceAnnotation.is_enabled
_clock = time.perf_counter
_ids = itertools.count(1)
_lock = threading.Lock()
_ring: "collections.deque" = collections.deque(maxlen=CAPACITY)
_dropped = [0]
_local = threading.local()


def new_id() -> int:
    """A fresh number from the sequence span ids are drawn from."""
    return next(_ids)


def _record(rec: tuple) -> None:
    with _lock:
        if len(_ring) == _ring.maxlen:
            _dropped[0] += 1
        _ring.append(rec)


class _Off:
    """The shared context of a span that is off."""

    __slots__ = ()
    id = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **ints) -> None:
        pass


_OFF = _Off()


class _Span:
    __slots__ = ("name", "ints", "id", "parent", "start", "_ann")

    def __init__(self, name: str, ints: dict):
        self.name = name
        self.ints = ints
        self.id = next(_ids)

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.parent = stack[-1] if stack else 0
        stack.append(self.id)
        self._ann = TraceAnnotation(self.name, id=self.id, **self.ints)
        self._ann.__enter__()
        self.start = _clock()
        return self

    def set(self, **ints) -> None:
        """Ints known only once the span is open (a batch's rows)."""
        self.ints.update(ints)
        self._ann.set_metadata(**ints)

    def __exit__(self, *exc):
        end = _clock()
        self._ann.__exit__(*exc)
        _local.stack.pop()
        _record((self.name, self.start, end, threading.get_ident(), self.id,
                 self.parent) + tuple(self.ints.items()))
        return False


def span(name: str, **ints):
    """A context that marks the code it encloses as ``name``; its ints go
    to the trace and the ring.  The context's ``id`` names it (0 when
    off), and ``set(**ints)`` adds ints once it is open."""
    if not enabled():
        return _OFF
    return _Span(name, ints)


def interval(name: str, start: float, end: float, **ints) -> None:
    """Record an interval measured elsewhere (``perf_counter`` seconds)."""
    if enabled():
        _record((name, start, end, threading.get_ident(), next(_ids), 0)
                + tuple(ints.items()))


def collected(t0: float, t1: float) -> List[tuple]:
    """The records that start in ``[t0, t1)``, oldest first."""
    with _lock:
        recs = list(_ring)
    return [r for r in recs if t0 <= r[1] < t1]


def dropped() -> int:
    """Records dropped because the ring was full."""
    return _dropped[0]


def clear() -> None:
    """Empty the ring and zero the dropped count."""
    with _lock:
        _ring.clear()
        _dropped[0] = 0
