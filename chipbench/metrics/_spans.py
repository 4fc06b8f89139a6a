"""The program's own host spans and counters (``repro.spans``), read by
the sparse-format readers.  ``None`` where the program has no such module
or never ran the span or counter."""


def _spans():
    try:
        from repro import spans
    except ImportError:
        return None
    return spans


def span_seconds(name: str):
    """Total seconds of the program's span ``name``."""
    spans = _spans()
    total = spans.totals().get(name) if spans else None
    return total.seconds if total else None


def counter(name: str):
    """The program's counter ``name``."""
    spans = _spans()
    return spans.counters().get(name) if spans else None
