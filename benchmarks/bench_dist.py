"""Distributed plan replay: 1-vs-N simulated devices (DESIGN.md §7).

Row labels: ``tuned-single`` is the single-device autotuned
``execute_plan`` baseline; ``collective`` the shard_map engine running
the model-picked plan with psum (deterministic row — no measurement in
the loop); ``tuned-replay`` the per-shard path (each shard through its
own cached tuned winner, host-side sum).  Host-CPU fake devices emulate the
collective structure; wall-clock on one host is NOT hardware scaling —
the rows exist so the distributed path sits in the perf trajectory
(BENCH_pr3.json) and a schedule regression in either engine trips the
CI gate.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

SNIPPET = """
import json
import os
import tempfile
import time
import numpy as np
import jax
import jax.numpy as jnp
from repro.autotune import TunerConfig
from repro.core import spec as S
from repro.core.executor import CSFArrays, make_executor
from repro.core.planner import plan
from repro.distributed import make_distributed, make_distributed_tuned
from repro.sparse import build_csf, random_sparse

n = len(jax.devices())
N = int(os.environ["BD_N"])
R = 16
cfg = TunerConfig(max_paths=2, max_candidates=2, orders_per_path=1,
                  warmup=1, repeats=2)
rng = np.random.default_rng(0)


def bench(fn):
    for _ in range(2):
        out = fn()
    jax.block_until_ready(out) if hasattr(out, "block_until_ready") else out
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = fn()
        if hasattr(out, "block_until_ready"):
            jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


rows = []
for name, spec in [("mttkrp", S.mttkrp(N, N, N, R)),
                   ("ttmc", S.ttmc3(N, N, N, R, 8))]:
    T = random_sparse((N, N, N), 5e-3, seed=2)
    csf = build_csf(T)
    factors = {t.name: jnp.asarray(rng.standard_normal(
        [spec.dims[i] for i in t.indices]).astype(np.float32))
        for t in spec.inputs if not t.is_sparse}
    cache = tempfile.mkdtemp()
    if n == 1:
        tuned = plan(spec, autotune=True, cache_dir=cache, csf=csf,
                     tuner=cfg)
        ex = make_executor(spec, tuned.path, tuned.order,
                           backend=tuned.backend)
        arrays = CSFArrays.from_csf(csf)
        fn = jax.jit(lambda f, ex=ex, a=arrays: ex(a, f))
        rows.append((name, "tuned-single", n, bench(lambda: fn(factors))))
    else:
        mesh = jax.make_mesh((n,), ("data",))
        # collective shard_map engine replaying one (model-picked) plan
        pl_ = plan(spec, nnz_levels=csf.nnz_levels())
        coll = make_distributed(spec, pl_, T, mesh, {0: "data"})
        rows.append((name, "collective", n,
                     bench(lambda: coll(factors))))
        # per-shard tuned replay (each shard through its cached winner)
        replay = make_distributed_tuned(spec, T, mesh, {0: "data"},
                                        cache_dir=cache, tuner=cfg,
                                        prefer_collective=False)
        rows.append((name, "tuned-replay", n,
                     bench(lambda: replay(factors))))
print(json.dumps(rows))
"""


def run(scale: float = 1.0):
    rows = [("bench", "kernel", "schedule", "devices", "us_per_call")]
    N = max(32, int(128 * scale))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    print("# dist: child processes on fake CPU devices "
          "(JAX_PLATFORMS=cpu); these rows are CPU timings", flush=True)
    for n in (1, 4):
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        # fake CPU devices, and off the chip: the parent process already
        # holds it, so a child that wanted it would fail or hang
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.path.join(repo, "src")
        env["BD_N"] = str(N)
        out = subprocess.run([sys.executable, "-c", SNIPPET], env=env,
                             capture_output=True, text=True, timeout=600)
        if out.returncode != 0:
            raise RuntimeError(
                f"bench_dist subprocess (n={n}) failed:\n"
                f"{out.stderr[-2000:]}")
        for kernel, schedule, devices, us in json.loads(
                out.stdout.strip().splitlines()[-1]):
            rows.append(("dist", kernel, schedule, devices, round(us, 1)))
    emit(rows)
    return rows


if __name__ == "__main__":
    run()
