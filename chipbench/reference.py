"""Plain references for the harness's correctness check, and the control.

The float64 references are numpy over the harness's own coordinates and
values: they import nothing of the program and read nothing it made except
the inputs and outputs of the step under test.

* ``mttkrp_rows`` / ``ttmc_rows``: the sparse kernel on a sample of output
  rows, from the factors the step was given.
* ``als_residual`` / ``hooi_projector``: the dense update on the sampled
  rows, against the reference kernel's rows and the step's inputs.

The control is the same arithmetic, in JAX, one precision step below what
the configuration states: bfloat16 operands for the float32 sparse kernels,
``Precision.HIGH`` (three bfloat16 passes) for the dense algebra the
configuration runs at ``HIGHEST``.  ``chipbench/control.py`` puts it in the
program's place to show that the check fails it.
"""
from __future__ import annotations

import numpy as np

#: nonzeros per block of the row references (bounds host memory)
BLOCK = 1 << 20

#: the control's matmul precision: one step below the configuration's
LOWER = {"highest": "high", "high": "default"}


def sample_rows(n: int, count: int, heaviest: int,
                rng: np.random.Generator) -> np.ndarray:
    """``count`` distinct rows of ``0..n-1`` drawn from ``rng``, plus the
    row that holds the most nonzeros; sorted."""
    rows = rng.choice(n, size=min(count, n), replace=False)
    return np.unique(np.append(rows, heaviest))


def _rows_of(coords: np.ndarray, mode: int, rows: np.ndarray):
    """Indices of the nonzeros whose ``mode`` coordinate is in ``rows``,
    and each one's position in ``rows``."""
    idx = np.flatnonzero(np.isin(coords[:, mode], rows))
    return idx, np.searchsorted(rows, coords[idx, mode])


def mttkrp_rows(coords, values, mode: int, rows, f1, f2) -> np.ndarray:
    """``out[r, :] = sum over nonzeros (i, j, k) with i_mode = rows[r] of
    v * f1[j_o1] * f2[k_o2]`` in float64, where ``o1 < o2`` are the other
    modes."""
    o1, o2 = (m for m in range(3) if m != mode)
    f1, f2 = np.asarray(f1, np.float64), np.asarray(f2, np.float64)
    idx, pos = _rows_of(coords, mode, rows)
    rank = f1.shape[1]
    out = np.zeros(len(rows) * rank)
    cols = np.arange(rank)
    for s in range(0, len(idx), BLOCK):
        b = idx[s:s + BLOCK]
        prod = (values[b].astype(np.float64)[:, None]
                * f1[coords[b, o1]] * f2[coords[b, o2]])
        out += np.bincount((pos[s:s + BLOCK, None] * rank + cols).ravel(),
                           prod.ravel(), minlength=out.size)
    return out.reshape(len(rows), rank)


def ttmc_rows(coords, values, mode: int, rows, u1, u2) -> np.ndarray:
    """``out[r, a * r2 + b] = sum of v * u1[j_o1, a] * u2[k_o2, b]`` over
    the nonzeros of row ``rows[r]`` of ``mode``, in float64: the mode's
    TTMc unfolded to ``(len(rows), r1 * r2)``."""
    o1, o2 = (m for m in range(3) if m != mode)
    u1, u2 = np.asarray(u1, np.float64), np.asarray(u2, np.float64)
    idx, pos = _rows_of(coords, mode, rows)
    width = u1.shape[1] * u2.shape[1]
    out = np.zeros(len(rows) * width)
    cols = np.arange(width)
    for s in range(0, len(idx), BLOCK // 4):
        b = idx[s:s + BLOCK // 4]
        left = values[b].astype(np.float64)[:, None] * u1[coords[b, o1]]
        prod = (left[:, :, None] * u2[coords[b, o2]][:, None, :]).reshape(
            len(b), width)
        out += np.bincount(
            (pos[s:s + BLOCK // 4, None] * width + cols).ravel(),
            prod.ravel(), minlength=out.size)
    return out.reshape(len(rows), width)


def als_residual(x_rows, m_rows, f1, f2, ridge: float) -> float:
    """Residual of the ALS normal equations on the sampled rows,
    ``max |x (f1'f1 * f2'f2 + ridge I) - m| / max |m|``, in float64, for
    an update ``x`` and the reference's MTTKRP rows ``m``."""
    f1, f2 = np.asarray(f1, np.float64), np.asarray(f2, np.float64)
    gram = (f1.T @ f1) * (f2.T @ f2) + ridge * np.eye(f1.shape[1])
    x = np.asarray(x_rows, np.float64)
    if not np.all(np.isfinite(x)):
        return float("inf")
    return rel_err(x @ gram, m_rows)


def hooi_projector(y_full, y_rows, r: int) -> np.ndarray:
    """The HOOI update's projector ``U U'`` on the sampled rows, in
    float64, where ``U`` holds the ``r`` leading left singular vectors of
    the reference's ``Y``.

    ``U = Y V_r S_r^-1``, with ``V_r, S_r`` from the SVD of ``y_full``
    (the whole unfolded ``Y`` the step produced; the sampled rows alone do
    not fix them) and ``Y`` on the sampled rows from the reference kernel,
    ``y_rows``.  The projector does not depend on the sign or rotation of
    the singular vectors within the leading subspace.
    """
    _, s, vt = np.linalg.svd(np.asarray(y_full, np.float64),
                             full_matrices=False)
    u_rows = np.asarray(y_rows, np.float64) @ (vt[:r].T / s[:r])
    return u_rows @ u_rows.T


def rel_err(out, ref) -> float:
    """Largest absolute error over the largest absolute reference value."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.all(np.isfinite(out)):
        return float("inf")
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-300))


# --------------------------------------------------------------------- #
# the control: the reference in JAX, one precision step down

def control_kernels(coords, values, dims, kind: str, chunk: int = 1 << 21):
    """Per-mode jitted kernels over the COO tensor, with bfloat16
    operands and float32 sums; same call signature as the program's.

    The nonzeros go through in chunks of ``chunk`` (zero-padded), so the
    lane-padded ``(chunk, width)`` products fit the chip at a cell's size.
    """
    import jax
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    nnz = len(values)
    chunk = min(chunk, nnz)
    pad = -nnz % chunk
    steps = (nnz + pad) // chunk

    def padded(x):
        return jnp.asarray(np.concatenate([x, np.zeros(pad, x.dtype)])
                           .reshape(steps, chunk))

    v = padded(values).astype(bf16)
    out = []
    for mode in range(3):
        o1, o2 = (m for m in range(3) if m != mode)
        i, j, k = (padded(coords[:, m]) for m in (mode, o1, o2))
        n = dims[mode]
        left_name, right_name = (("F1", "F2") if kind == "mttkrp"
                                 else ("U1", "U2"))

        def fn(f, i=i, j=j, k=k, n=n, l_name=left_name, r_name=right_name):
            a, b = f[l_name].astype(bf16), f[r_name].astype(bf16)

            def step(s, acc):
                left = v[s][:, None] * a[j[s]]
                right = b[k[s]]
                if kind == "mttkrp":
                    prod = left * right
                else:
                    prod = (left[:, :, None] * right[:, None, :]).reshape(
                        chunk, -1)
                return acc + jax.ops.segment_sum(
                    prod.astype(jnp.float32), i[s], num_segments=n)

            width = a.shape[1] * (1 if kind == "mttkrp" else b.shape[1])
            acc = jax.lax.fori_loop(0, steps, step,
                                    jnp.zeros((n, width), jnp.float32))
            return acc if kind == "mttkrp" else acc.reshape(
                n, a.shape[1], b.shape[1])

        out.append(jax.jit(fn))
    return out
