"""Share (%) of the engines' sorted segment reductions lowered to the
one-hot Pallas kernel: 100 x ``reduce.kernel`` / (``reduce.kernel`` +
``reduce.scatter``), the program's counters of each lowering (the
kernel, or XLA's scatter-add).  It says whether the kernel engaged;
``None`` where the program counts neither."""
from chipbench.metrics._spans import counter


def read(run):
    kernel = counter("reduce.kernel") or 0
    scatter = counter("reduce.scatter") or 0
    if kernel + scatter == 0:
        return None
    return 100.0 * kernel / (kernel + scatter)
