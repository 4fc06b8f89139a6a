"""Seconds the program spent deriving every mode's fiber coordinates and
segment maps on the host and putting them on the device, until they were
there (``CSFArrays.from_csf``; span ``csf.upload``)."""
from chipbench.metrics._spans import span_seconds


def read(run):
    return span_seconds("csf.upload")
