"""Share (%) of the SpTTN programs' device op time spent lifting operands
onto fibers: ops under a ``t<i>.lift`` scope (``chipbench/scopes.py``)."""
from chipbench import scopes


def read(run):
    return scopes.spttn_share(run, {"lift"})
