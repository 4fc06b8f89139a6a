"""Tensor completion with SGD on observed entries: the gradient's
cost-dominant kernels are TTTP (residual, Eq. 3) and MTTKRP-like products
(paper §3) — all planned by the framework.

    PYTHONPATH=src python examples/tensor_completion.py
"""
import numpy as np

import jax
import jax.numpy as jnp

from repro import (CSFArrays, build_csf, plan, plan_executor,
                   random_sparse, tttp3)
from repro.compile_cache import enable_compile_cache


def main(steps: int = 300, rank: int = 12, lr: float = 0.05):
    I, J, K = 96, 80, 64
    rng = np.random.default_rng(0)
    A0, B0, C0 = (rng.standard_normal((n, rank)).astype(np.float32) * 0.5
                  for n in (I, J, K))
    omega = random_sparse((I, J, K), 8e-3, seed=4)   # observed entries
    truth = (A0[omega.coords[:, 0]] * B0[omega.coords[:, 1]]
             * C0[omega.coords[:, 2]]).sum(1)
    csf = build_csf(omega)
    arrays = CSFArrays.from_csf(csf)
    obs = jnp.asarray(truth)

    spec = tttp3(I, J, K, rank)
    p = plan(spec, nnz_levels=csf.nnz_levels())
    ex = plan_executor(p)
    import dataclasses
    ones_arrays = dataclasses.replace(arrays,
                                      values=jnp.ones_like(arrays.values))

    def loss(params):
        A, B, C = params
        est = ex(ones_arrays, {"U": A, "V": B, "W": C})
        return 0.5 * jnp.mean((est - obs) ** 2)

    params = tuple(jnp.asarray(rng.standard_normal((n, rank))
                               .astype(np.float32)) * 0.4
                   for n in (I, J, K))
    val_grad = jax.jit(jax.value_and_grad(loss))
    m = tuple(jnp.zeros_like(p_) for p_ in params)
    vv = tuple(jnp.zeros_like(p_) for p_ in params)
    v0 = None
    for it in range(steps):
        v, g = val_grad(params)
        v0 = float(v) if v0 is None else v0
        m = tuple(0.9 * m_ + 0.1 * g_ for m_, g_ in zip(m, g))
        vv = tuple(0.99 * v_ + 0.01 * g_ * g_ for v_, g_ in zip(vv, g))
        t = it + 1
        params = tuple(
            p_ - lr * (m_ / (1 - 0.9 ** t))
            / (jnp.sqrt(v_ / (1 - 0.99 ** t)) + 1e-8)
            for p_, m_, v_ in zip(params, m, vv))
        if it % 25 == 0 or it == steps - 1:
            print(f"step {it:4d}  mse {float(v):.5f}", flush=True)
    assert float(v) < 0.25 * v0, (float(v), v0)
    print("completion converged")


if __name__ == "__main__":
    enable_compile_cache()
    main()
