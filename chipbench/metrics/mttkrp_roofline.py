"""Roofline share (%) of the MTTKRP programs (``chipbench/work.py``)."""
from chipbench.metrics._roofline import kernel_roofline


def read(run):
    return kernel_roofline(run, "mttkrp")
