"""GiB of the fiber arrays the SpTTN programs' contractions write in one
call of each, so in one sweep (a program per mode): the program counter
``contract.fiber_bytes``, which each engine adds once per term at trace
time (``VectorizedExecutor._count_fiber_bytes``).  A fused Pallas stage
writes none.  ``None`` where the program has no such counter, and in a
run that tuned (span ``tune.measure``), whose candidate engines add theirs
too."""
from chipbench.metrics._spans import counter, span_seconds


def read(run):
    n = counter("contract.fiber_bytes")
    if n is None or span_seconds("tune.measure") is not None:
        return None
    return n / 2.0 ** 30
