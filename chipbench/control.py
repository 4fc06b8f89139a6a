"""The control: the reference one precision step down, in the program's
place, at a cell's own size (``reference.control_kernels``).

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds 5

One process, on the chip.  For each seed it runs the cell's job with the
control's kernels and dense updates instead of the program's (no CSF, plan
or SpTTN program), for one warm-up sweep and a short window, and prints
one JSON line with the numbers the cell's check compares and their
limits.  The check must fail the control: its readings are the upper ends
the limits in the configuration were set below.  The benchmark's own runs
never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from chipbench import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.resolve_cell(args.workload, bench)
    cfg = harness.load_json(harness.HERE / "configs"
                            / f"{cell['config']}.json")
    mix = harness.load_json(harness.HERE / "mixes"
                            / f"{cell['traffic']}.json")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not cfg.get("rehearsal"):
        harness.log("control.py: JAX found no TPU; nothing was run")
        return 2
    harness.enable_compile_cache()
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(cell, cfg, mix, seed)
        job = harness.load_job(run, impl="control")
        for _ in range(mix["warmup_sweeps"]):
            harness.sweep(job)
        times, window_s = harness.run_window(job, args.seconds)
        checks = job.check()
        fails = [k for k, v in checks.items() if not v <= cfg["limits"][k]]
        failed_all &= bool(fails)
        print(json.dumps({
            "workload": cell["name"], "seed": seed, "impl": "control",
            "updates": len(times), "window_s": window_s,
            "fails": fails,
            "checks": {k: {"value": v, "limit": cfg["limits"][k]}
                       for k, v in checks.items()}}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
