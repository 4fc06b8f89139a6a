"""Share (%) of the traced window in which no op ran on the device:
``1 - busy / window``, busy being the union of device-op intervals."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
