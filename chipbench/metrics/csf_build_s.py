"""Seconds of set-up in the sparse format layer: the program's
``permute_modes`` -> ``build_csf`` -> ``CSFArrays.from_csf`` for every
mode, on the host clock (span ``csf_build``)."""


def read(run):
    return run.spans.total("csf_build") or None
