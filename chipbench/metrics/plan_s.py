"""Seconds of set-up in the planner and tuner: ``plan(autotune=True)`` for
every mode, through the checkout's plan cache, on the host clock (span
``plan``).  The run's log gives each mode's ``SearchStats.cache_hit``."""


def read(run):
    return run.spans.total("plan") or None
