"""End-to-end driver: sparse CP decomposition via ALS, every MTTKRP planned
by the SpTTN framework (the paper's flagship application).  Each kernel
runs on the engine its plan names (the tuned backend under --autotune),
jitted with the sparse operand as an argument.

    PYTHONPATH=src python examples/cp_als.py [--steps 200]
    PYTHONPATH=src python examples/cp_als.py --autotune --cache-dir .plans
        # measured search per mode-permuted MTTKRP; a re-run (or any later
        # tensor with the same sparsity profile) loads the plans from disk
"""
import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import (COOTensor, CSFArrays, build_csf, jit_bound, parse, plan,
                   plan_executor, random_sparse, tttp3)
from repro.compile_cache import enable_compile_cache

#: the normal equations are float32 algebra; TPUs run a default-precision
#: float32 matmul as one bfloat16 pass
HIGHEST = jax.lax.Precision.HIGHEST


def mode_mttkrp(coo: COOTensor, mode: int, rank: int):
    """The mode-``mode`` MTTKRP of ``coo``: its spec, over a CSF whose
    storage is permuted so the output mode leads."""
    perm = (mode,) + tuple(m for m in range(3) if m != mode)
    csf_m = build_csf(coo.permute_modes(perm))
    dims = dict(zip("ijk", csf_m.shape))
    spec = parse("ijk,ja,ka->ia", dims={**dims, "a": rank}, sparse=0,
                 names=["T", "F1", "F2"])
    return spec, csf_m


def cp_als(coo: COOTensor, rank: int, steps: int, seed: int = 0,
           autotune: bool = False, cache_dir: str | None = None,
           tuner=None):
    I, J, K = coo.shape
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((I, rank)).astype(np.float32)) * .1
    B = jnp.asarray(rng.standard_normal((J, rank)).astype(np.float32)) * .1
    C = jnp.asarray(rng.standard_normal((K, rank)).astype(np.float32)) * .1

    def planned(label, spec, csf):
        p = plan(spec, nnz_levels=csf.nnz_levels(), autotune=autotune,
                 cache_dir=cache_dir, csf=csf, tuner=tuner)
        if autotune and p.stats is not None:
            how = "cache" if p.stats.cache_hit else (
                f"search ({p.stats.candidates_timed} timed)")
            print(f"{label}: plan from {how}, backend {p.backend}, "
                  f"fused {p.fused}, block {p.block}", flush=True)
        return plan_executor(p)

    # one planned MTTKRP per mode: permute storage so the output mode leads
    execs = {}
    for mode, name in ((0, "A"), (1, "B"), (2, "C")):
        spec, csf_m = mode_mttkrp(coo, mode, rank)
        execs[name] = jit_bound(planned(f"mode {name}", spec, csf_m),
                                csf_m)

    # TTTP-style residual on the observed entries
    spec_r = tttp3(I, J, K, rank)
    csf = build_csf(coo)
    exr = planned("fit (TTTP)", spec_r, csf)
    vals = jnp.asarray(coo.values)
    import dataclasses
    est_obs = jit_bound(exr, dataclasses.replace(
        CSFArrays.from_csf(csf), values=jnp.ones_like(vals)))

    @jax.jit
    def fit_from(est, A, B, C):
        """Standard sparse-CP fit = 1 - ||T - est||_F / ||T||_F, with
        ||est||^2 via the Hadamard-Gram identity (zeros included — sparse
        CP fits the zeros as true zeros, as in SPLATT)."""
        t2 = jnp.sum(vals ** 2)
        cross = jnp.sum(vals * est)
        gram = (jnp.matmul(A.T, A, precision=HIGHEST)
                * jnp.matmul(B.T, B, precision=HIGHEST)
                * jnp.matmul(C.T, C, precision=HIGHEST))
        est2 = jnp.sum(gram)
        resid = jnp.sqrt(jnp.maximum(t2 - 2 * cross + est2, 0.0))
        return 1.0 - resid / jnp.sqrt(t2)

    def fit(A, B, C):
        return fit_from(est_obs({"U": A, "V": B, "W": C}), A, B, C)

    @jax.jit
    def solve(mttkrp_out, F1, F2):
        G = (jnp.matmul(F1.T, F1, precision=HIGHEST)
             * jnp.matmul(F2.T, F2, precision=HIGHEST)
             + 1e-6 * jnp.eye(rank))
        with jax.default_matmul_precision("highest"):
            return jnp.linalg.solve(G, mttkrp_out.T).T

    hist = []
    for it in range(steps):
        A = solve(execs["A"]({"F1": B, "F2": C}), B, C)
        B = solve(execs["B"]({"F1": A, "F2": C}), A, C)
        C = solve(execs["C"]({"F1": A, "F2": B}), A, B)
        if it % 10 == 0 or it == steps - 1:
            r = float(fit(A, B, C))
            hist.append(r)
            print(f"iter {it:4d}  fit {r:.4f}", flush=True)
    return (A, B, C), hist


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument("--autotune", action="store_true",
                    help="measured loop-nest search instead of model-only")
    ap.add_argument("--cache-dir", default=None,
                    help="persist tuned plans here (skips re-search)")
    args = ap.parse_args()
    enable_compile_cache()
    # synthesize a tensor with known rank-8 structure + noise
    rng = np.random.default_rng(1)
    I, J, K, r0 = 128, 96, 80, 8
    A0, B0, C0 = (rng.standard_normal((n, r0)) for n in (I, J, K))
    T = random_sparse((I, J, K), 5e-3, seed=2)
    vals = (A0[T.coords[:, 0]] * B0[T.coords[:, 1]]
            * C0[T.coords[:, 2]]).sum(1).astype(np.float32)
    T.values[:] = vals + 0.01 * rng.standard_normal(len(vals))
    t0 = time.time()
    _, hist = cp_als(T, rank=args.rank, steps=args.steps,
                     autotune=args.autotune, cache_dir=args.cache_dir)
    print(f"done in {time.time()-t0:.1f}s; fit {hist[0]:.3f} -> "
          f"{hist[-1]:.3f}")
    assert hist[-1] > hist[0]


if __name__ == "__main__":
    main()
