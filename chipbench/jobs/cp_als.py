"""CP-ALS: closed-loop sweeps, three MTTKRP updates each.

Factors keep unit columns and the weights ``lambda`` apart, as SPLATT's
``cpd_als`` does.  Update of mode ``m``: ``M = MTTKRP_m(F_o1, F_o2)``
through the mode's SpTTN program, then ``X = M (F_o1'F_o1 * F_o2'F_o2 +
ridge I)^-1`` at the configuration's ``dense_precision``, ``lambda`` = the
column norms of ``X`` and ``F_m = X / lambda``, then a wait for ``F_m``.
The ridge keeps the Gram matrix, whose diagonal is 1, no worse conditioned
than ``1 + R / ridge`` however many sweeps run.  After the last mode the SPLATT
fit follows from the last MTTKRP with no extra sparse kernel:
``<T, est> = sum(lambda * F_2 * M_2)``.

Spans: ``update.m<k>`` around an update, ``solve`` around the dense
update's dispatch, ``sync`` around the wait, ``fit`` around the fit.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference
from chipbench.jobs.common import control_kernels, others, spttn_kernels

KIND = "mttkrp"
CHECKS = ("mttkrp_err", "update_err")


class Job:
    def __init__(self, run, impl: str = "program"):
        import jax
        import jax.numpy as jnp

        self.run = run
        cfg = run.cfg
        self.rank = cfg["rank"]
        self.dims = cfg["dims"]
        self.modes = len(self.dims)
        rng = np.random.default_rng([run.seed, 2])
        init = [rng.standard_normal((n, self.rank)) for n in self.dims]
        self.factors = [jax.device_put(
            (f / np.linalg.norm(f, axis=0)).astype(np.float32)) for f in init]
        self.t2 = float(np.sum(run.values.astype(np.float64) ** 2))
        self.fits = []
        self.last = {}
        prec = cfg["dense_precision"]
        if impl != "program":
            prec = reference.LOWER[prec]
        precision = jax.lax.Precision[prec.upper()]
        ridge = cfg["ridge"]
        rank = self.rank
        t2 = self.t2

        def gram(f1, f2):
            return (jnp.matmul(f1.T, f1, precision=precision)
                    * jnp.matmul(f2.T, f2, precision=precision))

        def solve(m_out, f1, f2):
            g = gram(f1, f2) + ridge * jnp.eye(rank, dtype=jnp.float32)
            with jax.default_matmul_precision(prec):
                x = jnp.linalg.solve(g, m_out.T).T
            lam = jnp.linalg.norm(x, axis=0)
            return x / lam, lam

        def fit(m_last, lam, a, b, c):
            g = gram(a, b) * jnp.matmul(c.T, c, precision=precision)
            est2 = lam @ g @ lam
            resid2 = t2 - 2.0 * jnp.sum(lam * c * m_last) + est2
            return 1.0 - jnp.sqrt(jnp.maximum(resid2, 0.0) / t2)

        self.solve = jax.jit(solve)
        self.fit = jax.jit(fit)
        if impl == "program":
            self.kernels = spttn_kernels(
                run, KIND, self._spec,
                lambda m: {"F1": (self.dims[others(m)[0]], rank),
                           "F2": (self.dims[others(m)[1]], rank)})
        else:
            self.kernels = control_kernels(run, KIND)
        self.kernels_info = [
            {"name": k.name, "kind": KIND, "mode": m,
             "dims": tuple(self.dims[o] for o in (m,) + others(m)),
             "ranks": (self.rank, self.rank)}
            for m, k in enumerate(self.kernels)]

    def _spec(self, mode, dims):
        from repro import parse
        return parse("ijk,ja,ka->ia", dims={**dict(zip("ijk", dims)),
                                            "a": self.rank},
                     sparse=0, names=["T", "F1", "F2"])

    def update(self, m: int) -> None:
        span = self.run.spans
        o1, o2 = others(m)
        with span(f"update.m{m}"):
            f1, f2 = self.factors[o1], self.factors[o2]
            m_out = self.kernels[m].call({"F1": f1, "F2": f2})
            with span("solve"):
                new, lam = self.solve(m_out, f1, f2)
            with span("sync"):
                new.block_until_ready()
        self.last[m] = (f1, f2, m_out, new, lam)
        self.factors[m] = new

    def end_sweep(self) -> None:
        with self.run.spans("fit"):
            m_out, _, lam = self.last[self.modes - 1][2:]
            self.fits.append(self.fit(m_out, lam, *self.factors))

    def check(self) -> dict[str, float]:
        """Largest error over the modes of the last sweep, on the sampled
        rows, against the float64 reference from the same input factors:
        each MTTKRP, and each update as the residual of its normal
        equations, ``X (F_o1'F_o1 * F_o2'F_o2 + ridge I) - M``, with ``X``
        the update before its columns were scaled and ``M`` the
        reference's MTTKRP."""
        run = self.run
        host = {m: tuple(np.asarray(x) for x in v)
                for m, v in self.last.items()}
        self.release()
        k_err = u_err = 0.0
        for m, (f1, f2, m_out, new, lam) in sorted(host.items()):
            rows = run.check_rows(m)
            ref = reference.mttkrp_rows(run.coords, run.values, m, rows,
                                        f1, f2)
            k_err = max(k_err, reference.rel_err(m_out[rows], ref))
            u_err = max(u_err, reference.als_residual(
                new[rows] * lam, ref, f1, f2, run.cfg["ridge"]))
        fits = [float(f) for f in self.fits_host]
        run.log(f"fit over the sweeps: first {fits[0]:.6g}, "
                f"last {fits[-1]:.6g}")
        return {"mttkrp_err": k_err, "update_err": u_err}

    def release(self) -> None:
        """Drop every device array the job holds (after the window)."""
        self.fits_host = [np.asarray(f) for f in self.fits]
        self.kernels = self.factors = self.last = self.fits = None
