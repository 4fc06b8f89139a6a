"""Seconds the program spent permuting and sorting the coordinates for
every mode's CSF (``COOTensor.permute_modes``; span ``coo.sort``)."""
from chipbench.metrics._spans import span_seconds


def read(run):
    return span_seconds("coo.sort")
