"""The serving layer's span recorder and compile counter.

``span(name, **attrs)`` times one stage of the host path. Each span is
kept as a :class:`Span` in a bounded in-memory ring of ``CAPACITY``
spans, oldest dropped first, and also opens a
``jax.profiler.TraceAnnotation`` of the same name: under an active
``jax.profiler`` trace the stages then appear in the host plane of the
``.xplane.pb``, on the same clock as the device's operations, so each
device-idle gap can be put down to the stage the host was in. With no
profiler running an annotation costs well under a microsecond.

The recorder is process-wide, like the profiler: every server in the
process records into the same ring, and a reader may look at it after
the server is gone. Times come from ``time.monotonic()``, the clock of
:class:`~repro.serve.db_search.DBSearchServer`'s default ``clock``. The
parent of a span is the innermost span open on the same thread.

``compiles()`` counts the programs JAX compiled (or loaded from its
persistent cache), each against the name of the innermost span open in
the compiling thread, or under ``None`` outside any span: a batch whose
stage recompiles shows up under that stage's name.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Iterator

import jax

CAPACITY = 1 << 17
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class Span:
    id: int                 # unique in the process, in order of opening
    name: str
    start: float            # time.monotonic() seconds
    end: float
    parent: int | None      # id of the enclosing span, None at the top
    attrs: dict


_ring: collections.deque[Span] = collections.deque(maxlen=CAPACITY)
_ids = itertools.count()
_open = threading.local()   # .stack: [(id, name)] of the thread's open spans
_compiles: collections.Counter = collections.Counter()
_lock = threading.Lock()


def _stack() -> list[tuple[int, str]]:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[dict]:
    """Record the enclosed block as a span named ``name``. Yields the
    span's attribute dict: what a stage learns only as it runs (a plan's
    tile count) can be added to it, and is kept in the ring (the
    profiler's annotation carries the attributes given here)."""
    stack = _stack()
    sid = next(_ids)
    parent = stack[-1][0] if stack else None
    stack.append((sid, name))
    start = time.monotonic()
    try:
        with jax.profiler.TraceAnnotation(name, **attrs):
            yield attrs
    finally:
        end = time.monotonic()
        stack.pop()
        _ring.append(Span(sid, name, start, end, parent, attrs))


def spans() -> list[Span]:
    """The spans in the ring, oldest first (in the order they ended)."""
    return list(_ring)


def compiles() -> dict[str | None, int]:
    """Programs compiled so far in the process, by innermost open span."""
    with _lock:
        return dict(_compiles)


def _count_compile(event: str, duration_secs: float, **kw) -> None:
    if event == COMPILE_EVENT:
        stack = _stack()
        with _lock:
            _compiles[stack[-1][1] if stack else None] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)
