"""Host spans and counters of the program, kept in memory.

``span(name)`` times a block of host work and adds it to per-name
aggregates: calls and total seconds.  A span inside an open span of the
same name, in the same thread, is counted once, by the outer one.  Under
the JAX profiler each span is also a ``TraceAnnotation``, so it lands on
the device trace's clock; with no profiler running that costs next to
nothing.  ``count`` adds to a counter.  ``span`` also decorates a
function: each call is a span.

Only aggregates are kept, never one record per event: a process that
builds a CSF per request would otherwise grow without bound.  Per-event
timing is the profiler trace's job.  Spans sit on set-up and per-call host
paths only, never inside a jitted function.

>>> from repro import spans
>>> spans.reset()
>>> with spans.span("outer") as s:
...     with spans.span("inner"):
...         pass
>>> t = spans.totals()
>>> t["outer"].calls, t["outer"].seconds == s.seconds
(1, True)
>>> t["inner"].seconds <= t["outer"].seconds
True
>>> spans.count("bytes", 3); spans.count("bytes", 4)
>>> spans.counters()
{'bytes': 7}
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from collections.abc import Iterator


@dataclasses.dataclass
class Total:
    """Aggregate of every closed span of one name."""
    calls: int = 0
    seconds: float = 0.0


class Span:
    """An open span; ``seconds`` is set when it closes (0 for a span
    counted by an enclosing one of its name)."""
    __slots__ = ("name", "seconds")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0


_lock = threading.Lock()
_totals: dict[str, Total] = {}
_counters: dict[str, int | float] = {}
_open = threading.local()     # .names: the spans open in this thread


@contextlib.contextmanager
def span(name: str) -> Iterator[Span]:
    """Time the block under ``name``; yields the :class:`Span`."""
    from jax.profiler import TraceAnnotation
    rec = Span(name)
    names = _open.__dict__.setdefault("names", set())
    if name in names:
        yield rec
        return
    names.add(name)
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(name):
            yield rec
    finally:
        rec.seconds = time.perf_counter() - t0
        names.discard(name)
        with _lock:
            t = _totals.setdefault(name, Total())
            t.calls += 1
            t.seconds += rec.seconds


def count(name: str, n: int | float) -> None:
    """Add ``n`` to the counter ``name``."""
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def totals() -> dict[str, Total]:
    """A copy of the span aggregates, by name."""
    with _lock:
        return {k: dataclasses.replace(v) for k, v in _totals.items()}


def counters() -> dict[str, int | float]:
    """A copy of the counters, by name."""
    with _lock:
        return dict(_counters)


def reset() -> None:
    """Clear every aggregate and counter (spans still open are kept)."""
    with _lock:
        _totals.clear()
        _counters.clear()
