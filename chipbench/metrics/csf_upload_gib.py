"""GiB of CSF arrays the program put on the device for every mode: values,
fiber coordinates and segment maps (``CSFArrays.from_csf``; counter
``csf.upload_bytes``)."""
from chipbench.metrics._spans import counter


def read(run):
    n = counter("csf.upload_bytes")
    return None if n is None else n / 2.0 ** 30
