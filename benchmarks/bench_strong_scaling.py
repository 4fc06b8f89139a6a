"""Paper Fig 9/10b: strong scaling of the distributed SpTTN (shard_map).

One subprocess per device count (fake host-CPU devices), each emitting a
JSON line per engine: ``collective`` is the XLA shard_map engine
(:func:`make_distributed`), ``collective-pallas`` the stacked generated-
kernel engine (:func:`make_distributed_pallas`, interpret mode on CPU —
its wall-clock is validation-grade, the row exists so the stacked path
sits in the perf trajectory).  Host wall-clock on one host is NOT
hardware scaling; the artifact of record is the per-device work (nnz)
printed alongside.

Error discipline (the bench-smoke CI lane): a failed device count
reports out-of-band on stderr and is dropped from the table — rows stay
schema-clean (``us_per_call`` is always a number) so the medians JSON
and the regression gate never ingest garbage.  Only if EVERY device
count fails does the suite raise.
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
import time
import numpy as np
import jax
import jax.numpy as jnp
from repro.core import spec as S
from repro.core.planner import plan
from repro.distributed.spttn_dist import (make_distributed,
                                          make_distributed_pallas)
from repro.sparse import build_csf, random_sparse

n = len(jax.devices())
N = int(os.environ["BSS_N"])
R = 16
spec = S.mttkrp(N, N, N, R)
T = random_sparse((N, N, N), 10.0 / (N * N), seed=2)
csf = build_csf(T)
rng = np.random.default_rng(0)
factors = {"B": jnp.asarray(rng.standard_normal((N, R)).astype(np.float32)),
           "C": jnp.asarray(rng.standard_normal((N, R)).astype(np.float32))}
pl = plan(spec, nnz_levels=csf.nnz_levels())
mesh = jax.make_mesh((n,), ("data",))

def bench(dist):
    out = dist(factors); jax.block_until_ready(out)
    ts = []
    for _ in range(5):
        t0 = time.perf_counter(); out = dist(factors)
        jax.block_until_ready(out); ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)

for mode, make in [
        ("collective", make_distributed),
        ("collective-pallas", make_distributed_pallas)]:
    dist = make(spec, pl, T, mesh, mode_axis={0: "data"})
    print(json.dumps({"mode": mode, "n": n, "us": bench(dist),
                      "nnz": int(T.nnz)}))
"""


def run(scale: float = 1.0):
    rows = [("bench", "mode", "devices", "us_per_call", "nnz")]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    print("# strong_scaling: child processes on fake CPU devices "
          "(JAX_PLATFORMS=cpu); these rows are CPU timings", flush=True)
    failures = []
    for n in (1, 2, 4, 8):
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        # fake CPU devices, and off the chip: the parent process already
        # holds it, so a child that wanted it would fail or hang
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.path.join(repo, "src")
        env["BSS_N"] = str(max(64, int(256 * scale)))
        out = subprocess.run([sys.executable, "-c", SNIPPET], env=env,
                             capture_output=True, text=True, timeout=600)
        if out.returncode != 0:
            failures.append(n)
            print(f"# strong_scaling: {n} devices FAILED\n"
                  f"{out.stderr[-2000:]}", file=sys.stderr, flush=True)
            continue
        for line in out.stdout.strip().splitlines():
            if not line.startswith("{"):
                continue
            data = json.loads(line)
            rows.append(("strong_scaling", data["mode"], data["n"],
                         round(data["us"], 1), data["nnz"]))
    if len(failures) == 4:
        raise RuntimeError(
            "strong_scaling: every device count failed (see stderr)")
    emit(rows)
    return rows


if __name__ == "__main__":
    run(scale=float(os.environ.get("SCALE", "1.0")))
