"""Host spans on the serving hot path, on exactly while a JAX profiler
session is active (``jax.profiler.start_trace`` or the profiler server).

``span(name, **attrs)`` is a context manager. With no profiler session it
returns a shared null context and records nothing. Under a session it
enters ``jax.profiler.TraceAnnotation(name, **attrs)``, so the span lands
in the profiler's trace on the clock of the device planes, and keeps
``(name, t0, t1, parent, attrs)`` in a bounded process-wide list stamped
with ``time.monotonic()``, so that code in the process can read the spans
without parsing the trace. ``parent`` is the span that was open on the
same thread when this one began. The list is process-wide because the
profiler session it mirrors is.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

# spans kept, the oldest go first: about seven traced windows of a
# four-engine fleet, which records ~4500 spans in a 5 s window
CAPACITY = 1 << 15


class Span(NamedTuple):
    name: str
    t0: float                      # time.monotonic() seconds
    t1: float
    parent: Optional[int]          # index in the list ``recorded`` returns
    attrs: dict


# rows [seq, name, t0, t1, parent seq, attrs]; t1 is None while open
_rows: "collections.deque[list]" = collections.deque(maxlen=CAPACITY)
_seq = itertools.count()


class _Open(threading.local):
    """Each thread's open spans (their seq), innermost last."""

    def __init__(self):
        self.stack: List[int] = []


_open = _Open()


class _Recorder:
    __slots__ = ("_ann", "_row")

    def __init__(self, name: str, attrs: dict):
        self._ann = TraceAnnotation(name, **attrs)
        self._row = [None, name, None, None, None, attrs]

    def set(self, **attrs) -> None:
        """Attributes known only when the span's work is done."""
        self._row[5].update(attrs)
        self._ann.set_metadata(**attrs)

    def __enter__(self):
        self._ann.__enter__()
        stack = _open.stack
        row = self._row
        row[0] = seq = next(_seq)
        row[4] = stack[-1] if stack else None
        stack.append(seq)
        _rows.append(row)
        row[2] = time.monotonic()
        return self

    def __exit__(self, *exc):
        self._row[3] = time.monotonic()
        _open.stack.pop()
        return self._ann.__exit__(*exc)


class _Null:
    """What ``span`` returns while no profiler session is active."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set(self, **attrs) -> None:
        pass

    def __bool__(self) -> bool:
        """False, so that attributes that take work to count are counted
        only under a session: ``if sp: sp.set(...)``."""
        return False


_NULL = _Null()


def span(name: str, **attrs):
    """A span around the enclosed host code, recorded only while a
    profiler session is active. The value bound by ``with ... as s`` has
    ``s.set(**attrs)`` for attributes counted inside the span."""
    if not TraceAnnotation.is_enabled():
        return _NULL
    return _Recorder(name, attrs)


def recorded() -> List[Span]:
    """The finished spans still held, oldest first. A parent that was
    dropped from the bounded list, or is still open, reads None."""
    rows = sorted((r for r in list(_rows) if r[3] is not None),
                  key=lambda r: r[0])
    index = {r[0]: i for i, r in enumerate(rows)}
    return [Span(r[1], r[2], r[3], index.get(r[4]), dict(r[5]))
            for r in rows]


def clear() -> None:
    _rows.clear()
