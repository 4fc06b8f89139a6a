"""Share (%) of the SpTTN programs' device op time spent in sparse
reductions: ops under a ``t<i>.reduce`` or ``t<i>.stage.reduce`` scope
(``chipbench/scopes.py``).

A fused Pallas chain (``t<i>.stage.chain``) reduces too, but its one
kernel also lifts and contracts, so it counts under none of the shares.
A change that moves a reduction into a chain therefore lowers this share
whether or not the reduction got faster: read it beside ``sweep_s``."""
from chipbench import scopes


def read(run):
    return scopes.spttn_share(run, {"reduce", "stage.reduce"})
