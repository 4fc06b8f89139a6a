"""Record the small chip trace that ``test_trace.py`` reads.

    python3 chipbench/tests/record_trace.py <out_dir>

Run on one TPU chip.  Under the profiler, inside a host span ``window``,
it runs ``CALLS`` updates shaped like the harness's: a gather and segment
sum jitted as ``spttn_demo_m0``, a small solve jitted as ``dense_demo``,
and a wait, under the spans ``update.m0``, ``solve`` and ``sync``; then
the host sleeps ``SLEEP_S`` inside a span ``host_sleep`` while the device
has nothing to do.  It writes ``<out_dir>/small.xplane.pb`` and
``<out_dir>/small.json``, which records what was run.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

CALLS = 5
SLEEP_S = 0.05


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    from chipbench.run import Spans

    if jax.devices()[0].platform != "tpu":
        print("record_trace: JAX found no TPU", file=sys.stderr)
        return 2
    n, rows, rank = 1 << 20, 4096, 16
    rng = np.random.default_rng(0)
    seg = jnp.asarray(np.sort(rng.integers(0, rows, n)).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, rows, n).astype(np.int32))
    f = jnp.asarray(rng.standard_normal((rows, rank), dtype=np.float32))

    def spttn_demo_m0(f):
        return jax.ops.segment_sum(f[idx], seg, num_segments=rows)

    def dense_demo(m, f):
        g = jnp.matmul(f.T, f, precision="highest") + jnp.eye(rank)
        return jnp.linalg.solve(g, m.T).T

    kernel, solve = jax.jit(spttn_demo_m0), jax.jit(dense_demo)
    solve(kernel(f), f).block_until_ready()
    spans = Spans()
    spans.annotate = True
    tdir = Path(out) / "raw"
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(str(tdir))
    with spans("window"):
        for _ in range(CALLS):
            with spans("update.m0"):
                m = kernel(f)
                with spans("solve"):
                    f = solve(m, f)
                with spans("sync"):
                    f.block_until_ready()
        with spans("host_sleep"):
            time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    pb = sorted(tdir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    shutil.copy(pb, Path(out) / "small.xplane.pb")
    shutil.rmtree(tdir)
    (Path(out) / "small.json").write_text(json.dumps({
        "calls": {"spttn_demo_m0": CALLS, "dense_demo": CALLS},
        "sleep_s": SLEEP_S, "spans": sorted(spans.names()),
        "device_kind": jax.devices()[0].device_kind}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
