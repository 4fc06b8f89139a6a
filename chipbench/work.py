"""Operations and bytes each kernel kind needs, for its roofline share.

Computed from the kernel's kind, its ranks, the tensor's dims in storage
order and its CSF level counts ``nnz^(I1..Ip)`` alone: never from a plan,
a path, a backend or a padded shape.  So a kernel's roofline reads the
same work whichever engine or loop nest implements it.

* bytes: the compulsory traffic.  One value and one index per nonzero, one
  index per fiber at each level above the leaves, each factor read once,
  the output written once.  float32 values and factors, int32 indices.
* operations: the factorized loop nest of the paper.  MTTKRP
  ``2 R (nnz + nnz^(IJ))``; TTMc3 ``2 (nnz R2 + nnz^(IJ) R1 R2)``.
"""
from __future__ import annotations

import json
from pathlib import Path

WORD = 4      # bytes of a float32 value or factor entry, and of an int32


def _sparse_bytes(levels: dict[int, int]) -> int:
    order = max(levels)
    fibers = sum(levels[p] for p in range(1, order))
    return WORD * (2 * levels[order] + fibers)


def work(kind: str, dims, ranks, levels: dict[int, int]) -> tuple[int, int]:
    """``(operations, bytes)`` of one call of a mode's kernel.

    ``dims`` is ``(I, J, K)`` with the output mode first, ``levels`` the
    level counts of that storage order (``levels[3] == nnz``), ``ranks``
    the factor widths of ``J`` and ``K``.
    """
    I, J, K = dims
    nnz, fib2 = levels[3], levels[2]
    if kind == "mttkrp":
        (r,) = set(ranks)
        ops = 2 * r * (nnz + fib2)
        dense = r * (J + K) + r * I
    elif kind == "ttmc3":
        r1, r2 = ranks
        ops = 2 * (nnz * r2 + fib2 * r1 * r2)
        dense = r1 * J + r2 * K + I * r1 * r2
    else:
        raise ValueError(f"no work model for kernel kind {kind!r}")
    return ops, _sparse_bytes(levels) + WORD * dense


def peaks(device_kind: str, path: Path | None = None) -> dict:
    """The chip's peaks from ``peaks.json``; a kind not in the table is an
    error, never a default."""
    table = json.loads((path or Path(__file__).with_name("peaks.json"))
                       .read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def roofline(ops: float, nbytes: float, seconds: float,
             peak: dict) -> tuple[float, str]:
    """Share (%) of the roofline reached in ``seconds``, and the bound
    (``"bytes"`` or ``"flops"``) that sets the least time."""
    t_flops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
