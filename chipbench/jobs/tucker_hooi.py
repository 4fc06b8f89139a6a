"""Tucker HOOI: closed-loop sweeps, three TTMc updates each.

Update of mode ``m``: ``Y = TTMc_m(U_o1, U_o2)``, shape ``(I_m, R1, R2)``,
through the mode's SpTTN program, then ``U_m`` = the ``R_m`` leading left
singular vectors of ``Y`` unfolded to ``(I_m, R1 R2)``, on the device at
the configuration's ``dense_precision``, then a wait for ``U_m``.  After
the last mode the core's norm ``||U_m' Y||`` follows with no extra sparse
kernel.

Spans: ``update.m<k>`` around an update, ``svd`` around the dense
update's dispatch, ``sync`` around the wait, ``fit`` around the core norm.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference
from chipbench.jobs.common import control_kernels, others, spttn_kernels

KIND = "ttmc3"
CHECKS = ("ttmc_err", "update_err")


class Job:
    def __init__(self, run, impl: str = "program"):
        import jax
        import jax.numpy as jnp

        self.run = run
        cfg = run.cfg
        self.ranks = cfg["ranks"]
        self.dims = cfg["dims"]
        self.modes = len(self.dims)
        rng = np.random.default_rng([run.seed, 2])
        self.factors = [
            jax.device_put(np.linalg.qr(rng.standard_normal((n, r)))[0]
                           .astype(np.float32))
            for n, r in zip(self.dims, self.ranks)]
        self.norms = []
        self.last = {}
        prec = cfg["dense_precision"]
        if impl != "program":
            prec = reference.LOWER[prec]

        def leading(y, r):
            with jax.default_matmul_precision(prec):
                u, _, _ = jnp.linalg.svd(y.reshape(y.shape[0], -1),
                                         full_matrices=False)
            return u[:, :r]

        def core_norm(y, u):
            with jax.default_matmul_precision(prec):
                return jnp.linalg.norm(u.T @ y.reshape(y.shape[0], -1))

        self.svd = jax.jit(leading, static_argnums=1)
        self.core_norm = jax.jit(core_norm)
        if impl == "program":
            self.kernels = spttn_kernels(
                run, KIND, self._spec,
                lambda m: {"U1": (self.dims[others(m)[0]],
                                  self.ranks[others(m)[0]]),
                           "U2": (self.dims[others(m)[1]],
                                  self.ranks[others(m)[1]])})
        else:
            self.kernels = control_kernels(run, KIND)
        self.kernels_info = [
            {"name": k.name, "kind": KIND, "mode": m,
             "dims": tuple(self.dims[o] for o in (m,) + others(m)),
             "ranks": tuple(self.ranks[o] for o in others(m))}
            for m, k in enumerate(self.kernels)]

    def _spec(self, mode, dims):
        from repro import parse
        r1, r2 = (self.ranks[o] for o in others(mode))
        return parse("ijk,jr,ks->irs",
                     dims={**dict(zip("ijk", dims)), "r": r1, "s": r2},
                     sparse=0, names=["T", "U1", "U2"])

    def update(self, m: int) -> None:
        span = self.run.spans
        o1, o2 = others(m)
        with span(f"update.m{m}"):
            u1, u2 = self.factors[o1], self.factors[o2]
            y = self.kernels[m].call({"U1": u1, "U2": u2})
            with span("svd"):
                new = self.svd(y, self.ranks[m])
            with span("sync"):
                new.block_until_ready()
        self.last[m] = (u1, u2, y, new)
        self.factors[m] = new

    def end_sweep(self) -> None:
        y, new = self.last[self.modes - 1][2:]
        with self.run.spans("fit"):
            self.norms.append(self.core_norm(y, new))

    def check(self) -> dict[str, float]:
        """Largest error over the modes of the last sweep, on the sampled
        rows, against the float64 reference from the same input factors:
        each TTMc, and each update as its projector ``U U'`` on the
        sampled rows (``reference.hooi_projector``)."""
        run = self.run
        host = {m: tuple(np.asarray(x) for x in v)
                for m, v in self.last.items()}
        self.release()
        k_err = u_err = 0.0
        for m, (u1, u2, y, new) in sorted(host.items()):
            rows = run.check_rows(m)
            y2 = y.reshape(y.shape[0], -1)
            ref = reference.ttmc_rows(run.coords, run.values, m, rows,
                                      u1, u2)
            k_err = max(k_err, reference.rel_err(y2[rows], ref))
            if not np.all(np.isfinite(y2)):
                u_err = float("inf")
                continue
            proj = reference.hooi_projector(y2, ref, self.ranks[m])
            u_rows = new[rows].astype(np.float64)
            u_err = max(u_err, reference.rel_err(u_rows @ u_rows.T, proj))
        norms = [float(x) for x in self.norms_host]
        run.log(f"core norm over the sweeps: first {norms[0]:.6g}, "
                f"last {norms[-1]:.6g}")
        return {"ttmc_err": k_err, "update_err": u_err}

    def release(self) -> None:
        """Drop every device array the job holds (after the window)."""
        self.norms_host = [np.asarray(x) for x in self.norms]
        self.kernels = self.factors = self.last = self.norms = None
