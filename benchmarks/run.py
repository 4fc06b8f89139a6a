"""Benchmark harness entry point — one benchmark per paper table/figure.

  Fig 8   -> bench_mttkrp        Fig 9c/§7 TTTP -> bench_tttp
  §7 TTMc -> bench_ttmc          Fig 10a        -> bench_tttc
  Fig 10c -> bench_index_order   Alg 1          -> bench_search
  Fig 9/10b -> bench_strong_scaling (1..8 fake devices x both shard_map
               engines — XLA collective and stacked Pallas; subprocesses
               pinned to the CPU, so this process alone may hold a chip)
  MoE-SpTTN integration          -> bench_moe_dispatch
  §5.2 + DESIGN.md §7            -> bench_dist (1-vs-N tuned plan replay)

Prints ``name,...,us_per_call,derived`` CSV rows.  SCALE env var shrinks or
grows tensor sizes (default 0.5 keeps the suite under ~2 min on CPU).

CI contract (the bench-smoke lane):
  * any suite raising makes the process exit nonzero — a broken benchmark
    fails the build instead of rotting silently;
  * BENCH_JSON=path writes per-row medians as JSON
    ``{suite: {"tensor|schedule": us_per_call}}`` for the regression gate
    (scripts/check_bench_regression.py against the committed baseline).
"""
from __future__ import annotations

import json
import os
import traceback


def medians(results: dict) -> dict:
    """Extract ``{suite: {row_key: us_per_call}}`` from the row lists the
    suites return.  Only rows under a ``us_per_call`` header participate —
    search-phase timings (``ms`` columns) are too machine-noisy to gate."""
    out: dict[str, dict[str, float]] = {}
    for suite, rows in results.items():
        if not isinstance(rows, list) or not rows:
            continue
        header = rows[0]
        if "us_per_call" not in header:
            continue
        idx = list(header).index("us_per_call")
        entries = {}
        for row in rows[1:]:
            try:
                entries["|".join(str(x) for x in row[:idx])] = float(row[idx])
            except (TypeError, ValueError):
                continue
        if entries:
            out[suite] = entries
    return out


def main() -> int:
    scale = float(os.environ.get("SCALE", "0.5"))
    from repro.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    from benchmarks import (bench_dist, bench_index_order,
                            bench_moe_dispatch, bench_mttkrp,
                            bench_outofcore, bench_search,
                            bench_serve_latency, bench_strong_scaling,
                            bench_ttmc, bench_tttc, bench_tttp)

    suites = [
        ("mttkrp", lambda: bench_mttkrp.run(scale=scale)),
        ("ttmc", lambda: bench_ttmc.run(scale=scale)),
        ("tttp", lambda: bench_tttp.run(scale=scale)),
        ("tttc", lambda: bench_tttc.run()),
        ("index_order", lambda: bench_index_order.run(
            N=max(64, int(256 * scale)))),
        ("search", bench_search.run),
        ("autotune", bench_search.run_autotune),
        ("moe_dispatch", bench_moe_dispatch.run),
        ("dist", lambda: bench_dist.run(scale=scale)),
        ("serve_latency", bench_serve_latency.run),
        ("outofcore", lambda: bench_outofcore.run(scale=scale)),
        ("strong_scaling", lambda: bench_strong_scaling.run(scale=scale)),
    ]

    results: dict[str, object] = {}
    failed: list[str] = []
    for name, fn in suites:
        print(f"# === {name} ===", flush=True)
        try:
            results[name] = fn()
        except Exception:
            traceback.print_exc()
            print(f"{name},ERROR", flush=True)
            failed.append(name)

    json_path = os.environ.get("BENCH_JSON")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(medians(results), f, indent=1, sort_keys=True)
        print(f"# medians -> {json_path}", flush=True)

    if failed:
        print(f"# FAILED suites: {','.join(failed)}", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
