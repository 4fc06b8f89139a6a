"""Smoke run of the SpTTN main path on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py                 # one chip: phases A, B, C
    python chip_smoke.py --chips 4       # four chips: the distributed phase

One process, no children.  It exits nonzero, before any phase, unless
JAX's first device is a TPU, and nonzero if any phase fails.  The last
line of standard output is ``{"ok": true, "device": {...}}``.  Times it
prints are smoke timings (one cold run, compiles included), not
benchmark numbers.

Data: a tensor with FROSTT nell-2's dims (12,092 x 9,184 x 28,818) and its
power-law skew on mode 0, ``--nnz`` nonzeros (default 4,000,000; nell-2
has 76,879,419), float32, generated from ``--seed``.  Factors are random,
from the same seed.  The kernel is the mode-0 MTTKRP at R=16.

* Phase A, the tuned path: ``plan(autotune=True)`` over the backends the
  tuner uses on this device, then ``execute_plan`` on the winner.
* Phase B, the compiled kernels: the ``pallas`` engine with
  ``interpret=False`` under each strategy.  Each program must contain a
  Mosaic kernel (``tpu_custom_call``).  ``row`` and ``fused`` pad every
  level-2 fiber (millions of them, mostly one nonzero long) to a whole
  block, which does not fit the device at full size, so they run on a
  cut of the same tensor (``FORCED_NNZ`` nonzeros, block 8).
* Phase C, CP-ALS: two sweeps of ``examples/cp_als.py``'s ``cp_als``,
  every kernel on its tuned backend; the fit must not fall.
* ``--chips 4``: ``make_distributed_tuned`` over a 4-chip mesh on mode 0,
  then the collective engines forced once each.

Every result is checked against a float64 numpy reference on a seeded
sample of output rows, and against the XLA engine, at the relative
tolerance ``TOL`` (max abs error over max abs reference value).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
NELL2_DIMS = (12_092, 9_184, 28_818)
NELL2_NNZ = 76_879_419
RANK = 16
FORCED_NNZ = 100_000       # the cut for the forced row / fused strategies
FORCED_BLOCK = 8
SAMPLE_ROWS = 256
#: float32 parity: max |out - ref| / max |ref| over the sampled rows
TOL = 1e-4
TUNER = dict(max_candidates=2, orders_per_path=1, repeats=2)


def log(msg: str) -> None:
    print(msg, flush=True)


class Smoke:
    """The data, references and checks the phases share."""

    def __init__(self, nnz: int, seed: int):
        import numpy as np

        from cp_als import mode_mttkrp
        from repro import CSFArrays, random_sparse

        self.np = np
        self.seed = seed
        t0 = time.perf_counter()
        total = float(np.prod([float(d) for d in NELL2_DIMS]))
        self.coo = random_sparse(NELL2_DIMS, nnz / total, seed=seed,
                                 distribution="frostt")
        self.spec, self.csf = mode_mttkrp(self.coo, 0, RANK)
        self.arrays = CSFArrays.from_csf(self.csf)
        rng = np.random.default_rng(seed)
        self.factors_np = {
            "F1": rng.standard_normal((NELL2_DIMS[1], RANK)).astype(
                np.float32),
            "F2": rng.standard_normal((NELL2_DIMS[2], RANK)).astype(
                np.float32)}
        import jax.numpy as jnp
        self.factors = {k: jnp.asarray(v) for k, v in self.factors_np.items()}
        self.rows = np.sort(rng.choice(NELL2_DIMS[0], SAMPLE_ROWS,
                                       replace=False))
        self.rows[0] = 0          # the heaviest row of the skew
        self.rows = np.unique(self.rows)
        log(f"data: nell-2 dims {NELL2_DIMS}, frostt skew, seed {seed}: "
            f"{self.coo.nnz:,} nnz, reduced from nell-2's {NELL2_NNZ:,} "
            f"({NELL2_NNZ / self.coo.nnz:.1f}x fewer); CSF fibers "
            f"{self.csf.nfib}; built in "
            f"{time.perf_counter() - t0:.1f}s")

    def reference(self, coo):
        """float64 MTTKRP rows ``self.rows`` of ``coo`` (mode 0)."""
        np = self.np
        keep = np.isin(coo.coords[:, 0], self.rows)
        c = coo.coords[keep]
        v = coo.values[keep].astype(np.float64)
        f1 = self.factors_np["F1"].astype(np.float64)
        f2 = self.factors_np["F2"].astype(np.float64)
        ref = np.zeros((len(self.rows), RANK))
        np.add.at(ref, np.searchsorted(self.rows, c[:, 0]),
                  v[:, None] * f1[c[:, 1]] * f2[c[:, 2]])
        return ref

    def rel_err(self, out, ref) -> float:
        np = self.np
        out = np.asarray(out, np.float64)
        return float(np.abs(out - ref).max() / np.abs(ref).max())

    def check(self, label: str, out, ref, xla=None) -> None:
        """Compare ``out`` (full output) against the sampled float64
        reference and, when given, the XLA engine's full output."""
        np = self.np
        out = np.asarray(out)
        if not np.all(np.isfinite(out)):
            raise AssertionError(f"{label}: non-finite output")
        e_ref = self.rel_err(out[self.rows], ref)
        msg = f"{label}: max rel err vs float64 reference {e_ref:.3e}"
        errs = [e_ref]
        if xla is not None:
            e_xla = self.rel_err(out, np.asarray(xla, np.float64))
            msg += f", vs xla engine {e_xla:.3e}"
            errs.append(e_xla)
        log(msg + f" (tolerance {TOL:g})")
        if max(errs) > TOL:
            raise AssertionError(f"{label}: error {max(errs):.3e} over "
                                 f"tolerance {TOL:g}")


def compile_engine(ex, csf, factors):
    """Prepare the operand, compile ``ex`` for it, and return
    ``(compiled, operand, seconds)``."""
    import jax

    from repro import prepare_operand
    t0 = time.perf_counter()
    operand = prepare_operand(ex, csf, factors)
    compiled = jax.jit(ex.__call__).lower(operand, factors).compile()
    return compiled, operand, time.perf_counter() - t0


def phase_a(s: Smoke, cache_dir: str):
    """The tuned path: plan(autotune) -> execute_plan."""
    from repro import execute_plan, plan, plan_executor
    from repro.autotune import TunerConfig
    from repro.autotune.tuner import default_backends

    log(f"phase A: tuner backends on this device: {default_backends()}")
    t0 = time.perf_counter()
    p = plan(s.spec, autotune=True, cache_dir=cache_dir, csf=s.csf,
             factors=s.factors, tuner=TunerConfig(**TUNER))
    st = p.stats
    log(f"phase A: tune {time.perf_counter() - t0:.1f}s "
        f"(cache hit {st.cache_hit}, {st.candidates_timed} timed, "
        f"{st.pruned} pruned, {st.infeasible} did not fit the device)")
    ex = plan_executor(p)
    compiled, operand, secs = compile_engine(ex, s.csf, s.factors)
    strategy = (dict(ex.stage_strategy) if p.backend == "pallas"
                else "n/a")
    log(f"phase A: winner backend {p.backend}, fused {p.fused}, block "
        f"{p.block}, strategy {strategy}; compile {secs:.1f}s")
    ref = s.reference(s.coo)
    t0 = time.perf_counter()
    out = execute_plan(p, s.arrays, s.factors)
    out.block_until_ready()
    log(f"phase A: execute_plan {time.perf_counter() - t0:.2f}s")
    s.check("phase A execute_plan", out, ref)
    s.check("phase A jitted winner", compiled(operand, s.factors), ref)


def phase_b(s: Smoke) -> None:
    """Every Pallas strategy compiled (interpret=False) and checked."""
    from cp_als import mode_mttkrp
    from repro import make_executor, plan, random_sparse

    log("phase B: contraction precision: JAX's default (none set); the "
        "MTTKRP stages are elementwise products and sums, so neither "
        "engine makes a bfloat16 MXU pass")
    total = float(s.np.prod([float(d) for d in NELL2_DIMS]))
    cut = random_sparse(NELL2_DIMS, FORCED_NNZ / total, seed=s.seed,
                        distribution="frostt")
    _, cut_csf = mode_mttkrp(cut, 0, RANK)
    log(f"phase B: row and fused run on a cut: {cut.nnz:,} nnz "
        f"({s.coo.nnz / cut.nnz:.0f}x fewer), block {FORCED_BLOCK}")
    tensors = {"full": (s.coo, s.csf), "cut": (cut, cut_csf)}
    model = plan(s.spec, nnz_levels=s.csf.nnz_levels())
    for name, (coo, csf) in tensors.items():
        xla = make_executor(s.spec, model.path, model.order, backend="xla")
        compiled, operand, secs = compile_engine(xla, csf, s.factors)
        xla_out = compiled(operand, s.factors)
        ref = s.reference(coo)
        s.check(f"phase B xla ({name}, compile {secs:.1f}s)", xla_out, ref)
        tensors[name] = (coo, csf, xla_out, ref)
    for strategy in ("row", "segsum", "fused", "auto"):
        forced = strategy in ("row", "fused")
        coo, csf, xla_out, ref = tensors["cut" if forced else "full"]
        kw = {"block": FORCED_BLOCK} if forced else {}
        ex = make_executor(s.spec, model.path, model.order,
                           backend="pallas", interpret=False,
                           strategy=strategy, **kw)
        if ex.interpret is not False:
            raise AssertionError(f"{strategy}: engine is in interpret mode")
        compiled, operand, secs = compile_engine(ex, csf, s.factors)
        text = compiled.as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{strategy}: no Mosaic kernel compiled")
        t0 = time.perf_counter()
        out = compiled(operand, s.factors)
        out.block_until_ready()
        log(f"phase B pallas {strategy}: interpret=False, tpu_custom_call "
            f"x{text.count('tpu_custom_call')}, stages "
            f"{dict(ex.stage_strategy)}, block {ex.block}, compile "
            f"{secs:.1f}s, run {time.perf_counter() - t0:.3f}s")
        s.check(f"phase B pallas {strategy}", out, ref, xla_out)


def phase_c(s: Smoke, cache_dir: str) -> None:
    """Two CP-ALS sweeps, every kernel on its tuned backend."""
    from cp_als import cp_als
    from repro.autotune import TunerConfig

    t0 = time.perf_counter()
    _, hist = cp_als(s.coo, rank=RANK, steps=2, seed=s.seed, autotune=True,
                     cache_dir=cache_dir, tuner=TunerConfig(**TUNER))
    log(f"phase C: CP-ALS fits {hist} in {time.perf_counter() - t0:.1f}s")
    if not all(s.np.isfinite(hist)):
        raise AssertionError(f"phase C: non-finite fit {hist}")
    if hist[-1] < hist[0] - 1e-4:
        raise AssertionError(f"phase C: fit fell {hist[0]} -> {hist[-1]}")


def phase_dist(s: Smoke, cache_dir: str) -> None:
    """The distributed engines over a 4-chip mesh on mode 0."""
    import jax

    from repro import execute_plan, plan
    from repro.autotune import TunerConfig
    from repro.distributed.spttn_dist import (make_distributed,
                                              make_distributed_pallas,
                                              make_distributed_tuned,
                                              undo_cyclic)

    mesh = jax.make_mesh((4,), ("data",))
    mode_axis = {0: "data"}
    ref = s.reference(s.coo)
    model = plan(s.spec, nnz_levels=s.csf.nnz_levels())
    one = execute_plan(model, s.arrays, s.factors)      # device 0
    s.check("dist: execute_plan on device 0", one, ref)
    t0 = time.perf_counter()
    dist = make_distributed_tuned(s.spec, s.coo, mesh, mode_axis,
                                  cache_dir=cache_dir,
                                  tuner=TunerConfig(**TUNER))
    log(f"dist: make_distributed_tuned {time.perf_counter() - t0:.1f}s, "
        f"routed mode {dist.mode}, shard backends {dist.backends}, "
        f"nnz per shard {dist.nnz_per_shard}")
    if dist.collective is not None:
        _log_placement("dist tuned", dist.collective.stacked["values"])
    s.check(f"dist tuned ({dist.mode})", dist(s.factors), ref, one)
    # per-shard replay (what heterogeneous winners route to), forced
    replay = make_distributed_tuned(s.spec, s.coo, mesh, mode_axis,
                                    cache_dir=cache_dir,
                                    tuner=TunerConfig(**TUNER),
                                    prefer_collective=False)
    where = [f"shard {sh.index}: {list(sh.fn(s.factors).devices())}"
             for sh in replay.shards if sh.fn is not None]
    log(f"dist replay: routed mode {replay.mode}, each shard's output on "
        + ", ".join(where))
    s.check(f"dist tuned ({replay.mode})", replay(s.factors), ref, one)
    for name, make in (("make_distributed", make_distributed),
                       ("make_distributed_pallas", make_distributed_pallas)):
        t0 = time.perf_counter()
        d = make(s.spec, model, s.coo, mesh, mode_axis)
        out = d(s.factors)
        out.block_until_ready()
        log(f"dist {name}: built and ran in "
            f"{time.perf_counter() - t0:.1f}s")
        _log_placement(f"dist {name}", d.stacked["values"])
        out = undo_cyclic(s.np.asarray(out), s.spec, mode_axis, mesh,
                          s.coo.shape)
        s.check(f"dist {name}", out, ref, one)


def _log_placement(label: str, arr) -> None:
    log(f"{label}: operand shards on " + ", ".join(
        f"{sh.device} rows {sh.index[0].start}:{sh.index[0].stop}"
        for sh in arr.addressable_shards))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--nnz", type=int, default=4_000_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]
    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    cache_dir = str(ROOT / ".plan_cache")

    t_all = time.perf_counter()
    s = Smoke(args.nnz, args.seed)
    if args.chips == 4:
        phase_dist(s, cache_dir)
    else:
        t0 = time.perf_counter()
        phase_a(s, cache_dir)
        log(f"phase A done in {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        phase_b(s)
        log(f"phase B done in {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        phase_c(s, cache_dir)
        log(f"phase C done in {time.perf_counter() - t0:.1f}s")
    log(f"smoke total {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
