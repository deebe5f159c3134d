"""Host spans: named intervals on the profiler's clock, also kept in memory.

``span(name, **counts)`` opens a ``jax.profiler.TraceAnnotation``, so that
under a profiler session the interval lands on the host plane of the same
trace as the device's events, and times it with ``time.perf_counter``.
Spans nest per thread.  The outermost open span is a root; when it closes
its :class:`Record` replaces the previous one of that name, which
:func:`last` returns.  Nothing is written anywhere, and with no profiler
session open a span costs one annotation object and two clock reads.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from collections import defaultdict

import jax


@dataclasses.dataclass
class Record:
    """One finished root span: its seconds, the seconds of the spans
    under it summed by name, and the counts given to any of them (summed
    by key)."""

    name: str
    seconds: float = 0.0
    children: dict = dataclasses.field(default_factory=lambda: defaultdict(float))
    counts: dict = dataclasses.field(default_factory=lambda: defaultdict(int))


class _Open:
    """The handle ``span`` yields: adds counts known only inside the body."""

    def __init__(self, record: Record, annotation):
        self._record, self._annotation = record, annotation

    def count(self, **counts) -> None:
        for key, n in counts.items():
            self._record.counts[key] += n
        self._annotation.set_metadata(**counts)


_LOCAL = threading.local()
_LAST: dict[str, Record] = {}


@contextlib.contextmanager
def span(name: str, **counts):
    """Time the body as span ``name`` with ``counts``; yields a handle
    whose ``count(**counts)`` adds counts known only inside the body."""
    record = getattr(_LOCAL, "root", None)
    is_root = record is None
    if is_root:
        record = _LOCAL.root = Record(name)
    annotation = jax.profiler.TraceAnnotation(name, **counts)
    handle = _Open(record, annotation)
    for key, n in counts.items():
        record.counts[key] += n
    t0 = time.perf_counter()
    try:
        with annotation:
            yield handle
    finally:
        secs = time.perf_counter() - t0
        if is_root:
            _LOCAL.root = None
            record.seconds = secs
            _LAST[name] = record
        else:
            record.children[name] += secs


def last(name: str) -> Record | None:
    """The newest finished root span called ``name``, or ``None``."""
    return _LAST.get(name)
