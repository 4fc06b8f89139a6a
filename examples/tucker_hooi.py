"""Tucker decomposition via HOOI: the TTMc kernel (paper Eq. 2) planned and
executed by the framework, one mode-permuted CSF per mode (as SPLATT does).

    PYTHONPATH=src python examples/tucker_hooi.py [--autotune]
        [--cache-dir .plans]
"""
import argparse

import numpy as np

import jax.numpy as jnp

from repro import (build_csf, jit_bound, parse, plan, plan_executor,
                   random_sparse)
from repro.compile_cache import enable_compile_cache


def main(steps: int = 8, ranks=(8, 6, 4), autotune: bool = False,
         cache_dir: str | None = None):
    I, J, K = 96, 80, 64
    T = random_sparse((I, J, K), 5e-3, seed=3)
    rng = np.random.default_rng(0)
    U = [jnp.linalg.qr(jnp.asarray(rng.standard_normal((n, r))
                                   .astype(np.float32)))[0]
         for n, r in zip((I, J, K), ranks)]

    execs = []
    for mode in range(3):
        perm = (mode,) + tuple(m for m in range(3) if m != mode)
        csf_m = build_csf(T.permute_modes(perm))
        dims = dict(zip("ijk", csf_m.shape))
        r1, r2 = [ranks[m] for m in perm[1:]]
        spec = parse("ijk,jr,ks->irs",
                       dims={**dims, "r": r1, "s": r2}, sparse=0,
                       names=["T", "U1", "U2"])
        p = plan(spec, nnz_levels=csf_m.nnz_levels(), autotune=autotune,
                 cache_dir=cache_dir, csf=csf_m)
        if autotune and p.stats is not None:
            how = "cache" if p.stats.cache_hit else (
                f"search ({p.stats.candidates_timed} timed)")
            print(f"mode {mode}: plan from {how}", flush=True)
        execs.append(jit_bound(plan_executor(p), csf_m))

    for it in range(steps):
        for mode in range(3):
            others = [m for m in range(3) if m != mode]
            Y = execs[mode]({"U1": U[others[0]],
                             "U2": U[others[1]]})           # (I_m, r1, r2)
            Ym = np.asarray(Y).reshape(Y.shape[0], -1)
            u, s, _ = np.linalg.svd(Ym, full_matrices=False)
            U[mode] = jnp.asarray(u[:, : ranks[mode]])
        core_norm = float(np.linalg.norm(s[: ranks[2]]))
        print(f"sweep {it}: captured core norm {core_norm:.4f}", flush=True)
    print("HOOI done")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--autotune", action="store_true",
                    help="measured loop-nest search instead of model-only")
    ap.add_argument("--cache-dir", default=None,
                    help="persist tuned plans here (skips re-search)")
    args = ap.parse_args()
    enable_compile_cache()
    main(steps=args.steps, autotune=args.autotune,
         cache_dir=args.cache_dir)
