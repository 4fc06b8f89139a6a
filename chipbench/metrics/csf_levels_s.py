"""Seconds the program spent in the CSF level pass of every mode
(``build_csf``; span ``csf.levels``)."""
from chipbench.metrics._spans import span_seconds


def read(run):
    return span_seconds("csf.levels")
