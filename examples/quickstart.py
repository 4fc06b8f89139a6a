"""Quickstart: declare an SpTTN kernel, let the planner find the minimum
cost loop nest, execute it, and inspect the schedule — all through the
top-level ``repro`` facade.

    PYTHONPATH=src python examples/quickstart.py
    EX_SCALE=0.1 PYTHONPATH=src python examples/quickstart.py   # CI smoke
"""
import os

import numpy as np

import jax.numpy as jnp

from repro import (CSFArrays, build_csf, dense_oracle, execute_plan, mttkrp,
                   plan, plan_peak_bytes, random_sparse)
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

# MTTKRP (paper Eq. 1): A(i,a) = sum_jk T(i,j,k) B(j,a) C(k,a)
SCALE = float(os.environ.get("EX_SCALE", "1.0"))
I, J, K, R = (max(8, int(n * SCALE)) for n in (256, 128, 64, 32))
spec = mttkrp(I, J, K, R)

T = random_sparse((I, J, K), density=1e-3, seed=0)
csf = build_csf(T)
print(f"T: shape={T.shape} nnz={T.nnz} "
      f"nnz^(IJ)={csf.nnz_level(2)} nnz^(I)={csf.nnz_level(1)}")

# plan: enumerate min-depth contraction paths, run Algorithm 1 per path
p = plan(spec, nnz_levels=csf.nnz_levels())
print("\nchosen loop nest (factorize-and-fuse):")
print(p.describe())

rng = np.random.default_rng(0)
factors = {"B": jnp.asarray(rng.standard_normal((J, R)).astype(np.float32)),
           "C": jnp.asarray(rng.standard_normal((K, R)).astype(np.float32))}
arrays = CSFArrays.from_csf(csf)
out = execute_plan(p, arrays, factors)
oracle = dense_oracle(spec, csf, {k: np.asarray(v)
                                  for k, v in factors.items()})
err = float(np.abs(np.asarray(out) - oracle).max())
print("\nmax |out - dense einsum oracle| =", err)
assert err < 1e-3

# out-of-core replay (docs/out-of-core.md): cap the working set at half
# the unsliced peak and the same plan streams chunk by chunk, exactly
peak = plan_peak_bytes(spec, p.path, p.order, csf.nnz_levels())
sliced = execute_plan(p, arrays, factors, memory_budget=peak // 2)
print(f"peak working set {peak} B; replayed under {peak // 2} B budget, "
      f"max delta = {float(np.abs(np.asarray(sliced) - oracle).max()):.2e}")
assert np.allclose(np.asarray(sliced), np.asarray(out), atol=1e-4)
