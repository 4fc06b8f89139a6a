"""Seconds of the warm-up sweep: the first call of every jitted program
(compiled, or loaded from the compile cache) and one run of each, on the
host clock (span ``warmup``)."""


def read(run):
    return run.spans.total("warmup") or None
