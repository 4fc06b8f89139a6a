"""Roofline share (%) of the TTMc programs (``chipbench/work.py``)."""
from chipbench.metrics._roofline import kernel_roofline


def read(run):
    return kernel_roofline(run, "ttmc3")
