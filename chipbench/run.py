"""The benchmark's one command: one cell, one seed, one measured window.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/mixes/<traffic>.json``); the mix names the job
(``chipbench/jobs/<job>.py``) it drives, and each per-layer metric of the
cell is read by ``chipbench/metrics/<metric>.py``.  All are found by name.

The run exits with code 2, and prints no result, unless JAX's first device
is a TPU and there are as many as the cell asks for.  Only a configuration
whose file says ``"rehearsal": true`` (tiny shapes) may run on the CPU,
named ``<config>.<traffic>`` when it is not a cell of ``BENCHMARK.json``;
its numbers are printed under ``cpu_rehearsal.<metric>``, never under a
device metric's name.

Set-up (``setup_s``, from process start): the pattern (cached per
checkout), values and initial factors from ``--seed``, the program's CSF
builds, plans and compiles, and one warm-up sweep.  The window then runs
whole sweeps until ``--seconds`` have passed.  After it: the device's
peak memory, the job's state freed, and the comparison with the float64
reference that decides ``correct``.  With ``--trace 1`` the window runs
under the profiler and the per-layer metrics are printed instead of the
end-to-end ones.  The last line of standard output is the JSON result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
GIB = 2.0 ** 30


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def resolve_cell(name: str, bench: dict) -> dict:
    """The cell ``name`` of ``BENCHMARK.json``, or a rehearsal cell
    ``<config>.<traffic>`` whose configuration is marked as one."""
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return dict(cell)
    config, _, traffic = name.rpartition(".")
    path = HERE / "configs" / f"{config}.json"
    if config and path.exists() and load_json(path).get("rehearsal"):
        return {"name": name, "config": config, "traffic": traffic,
                "chips": 1}
    raise SystemExit(f"run.py: no cell {name!r} in BENCHMARK.json and no "
                     f"rehearsal configuration {config!r}")


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: its per-layer ones with ``trace``,
    else its end-to-end ones."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


class Spans:
    """Host spans of the harness, kept in memory; while ``annotate`` is
    on, each is also a profiler annotation on the trace's clock."""

    def __init__(self):
        self.done: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.done.append((name, t0, time.perf_counter()))

    def total(self, name: str) -> float:
        return sum(b - a for n, a, b in self.done if n == name)

    def names(self) -> set[str]:
        return {n for n, _, _ in self.done}


class Run:
    """What one run knows; passed to the job and to the metric readers."""

    def __init__(self, cell: dict, cfg: dict, mix: dict, seed: int):
        from chipbench import data
        self.cell, self.cfg, self.mix, self.seed = cell, cfg, mix, seed
        self.spans = Spans()
        self.log = log
        self.coords = data.load_pattern(cfg)
        self.values = data.draw_values(cfg["nnz"], seed)
        self.job = None
        self.trace = None          # trace.Summary of a traced window
        self.device_kind = None
        self._levels = None

    def levels(self, mode: int) -> dict[int, int]:
        """CSF level counts of ``mode``'s storage, from the harness's own
        coordinates."""
        if self._levels is None:
            from chipbench import data
            self._levels = data.pattern_levels(self.cfg, self.coords)
        return self._levels[mode]

    def check_rows(self, mode: int) -> np.ndarray:
        from chipbench import reference
        heaviest = int(np.argmax(np.bincount(self.coords[:, mode])))
        return reference.sample_rows(
            self.cfg["dims"][mode], self.cfg["check_rows"], heaviest,
            np.random.default_rng([self.seed, 3, mode]))


def load_job(run: Run, impl: str = "program"):
    module = importlib.import_module(f"chipbench.jobs.{run.mix['job']}")
    return module.Job(run, impl=impl)


def sweep(job) -> list[float]:
    """One sweep: every mode's update, each timed to its wait."""
    times = []
    for m in range(job.modes):
        t0 = time.perf_counter()
        job.update(m)
        times.append(time.perf_counter() - t0)
    job.end_sweep()
    return times


def run_window(job, seconds: float):
    """Whole sweeps until ``seconds`` have passed; returns the update
    times and the window's length."""
    times: list[float] = []
    t0 = time.perf_counter()
    while True:
        times += sweep(job)
        if time.perf_counter() - t0 >= seconds:
            return times, time.perf_counter() - t0


class CompileCounter:
    """Counts XLA compilations while ``on``."""

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, otherwise ``.jax_cache/`` in the checkout (a fixed path:
    the path is part of what a later run looks up).  Every program is
    kept, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def traced_window(run: Run, job, seconds: float):
    """The window under the profiler; sets ``run.trace``."""
    import jax

    from chipbench import trace as trace_lib
    tdir = HERE / ".cache" / "trace"
    shutil.rmtree(tdir, ignore_errors=True)
    run.spans.annotate = True
    jax.profiler.start_trace(str(tdir))
    try:
        with run.spans("window"):
            out = run_window(job, seconds)
    finally:
        jax.profiler.stop_trace()
        run.spans.annotate = False
    files = sorted(tdir.glob("plugins/profile/*/*.xplane.pb"))
    t0 = time.perf_counter()
    run.trace = trace_lib.reduce(trace_lib.load(str(files[-1])),
                                 run.spans.names())
    log(f"trace: {files[-1].stat().st_size / 2**20:.1f} MiB read in "
        f"{time.perf_counter() - t0:.1f}s")
    return out


def read_metric(name: str, run: Run):
    module = importlib.import_module(f"chipbench.metrics.{name}")
    return module.read(run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = resolve_cell(args.workload, bench)
    cfg = load_json(HERE / "configs" / f"{cell['config']}.json")
    mix = load_json(HERE / "mixes" / f"{cell['traffic']}.json")

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs outside
    import jax
    devices = jax.devices()
    dev = devices[0]
    rehearsal = bool(cfg.get("rehearsal"))
    if dev.platform != "tpu" and not rehearsal:
        log(f"run.py: JAX found no TPU (platform {dev.platform}); "
            "nothing was run")
        return 2
    if len(devices) < cell["chips"]:
        log(f"run.py: the cell needs {cell['chips']} chips, JAX found "
            f"{len(devices)}")
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    cache = enable_compile_cache()
    import repro  # noqa: F401  (the system under test; fails in a bare tree)
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache}")

    run = Run(cell, cfg, mix, args.seed)
    run.device_kind = dev.device_kind
    job = run.job = load_job(run)
    for k in job.kernels:
        log(f"{k.name}: backend {k.backend}, plan cache hit {k.cache_hit}, "
            f"{k.candidates_timed} candidates timed")
    with run.spans("warmup"):
        for _ in range(mix["warmup_sweeps"]):
            sweep(job)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.2f}s: csf_build {run.spans.total('csf_build'):.2f}"
        f"s, plan {run.spans.total('plan'):.2f}s, warmup "
        f"{run.spans.total('warmup'):.2f}s")

    compiles = CompileCounter()
    compiles.on = True
    if args.trace:
        times, window_s = traced_window(run, job, args.seconds)
    else:
        times, window_s = run_window(job, args.seconds)
    compiles.on = False
    peak = peak_bytes(devices[:cell["chips"]])
    sweeps = len(times) // job.modes
    log(f"window {window_s:.3f}s: {sweeps} sweeps, {len(times)} updates, "
        f"{compiles.count} compilations; peak device memory "
        f"{peak / GIB:.3f} GiB")
    log("update seconds: " + " ".join(f"{t:.4f}" for t in times))

    t_check = time.perf_counter()
    checks = job.check()
    log(f"reference check {time.perf_counter() - t_check:.1f}s")
    limits = cfg["limits"]
    failed = [k for k, v in checks.items()
              if not (np.isfinite(v) and v <= limits[k])]

    values = {}
    if args.trace:
        for m in cell_metrics(bench, cell["name"], trace=True):
            v = read_metric(m["name"], run)
            if v is not None:
                values[m["name"]] = (v, m["unit"])
    else:
        e2e = {"setup_s": setup_s,
               "sweep_s": window_s / sweeps,
               "update_p90_ms": 1e3 * float(np.quantile(times, 0.9)),
               "peak_hbm_gib": peak / GIB}
        for m in cell_metrics(bench, cell["name"], trace=False):
            values[m["name"]] = (e2e[m["name"]], m["unit"])
    if rehearsal and dev.platform != "tpu":
        values = {f"cpu_rehearsal.{k}": v for k, v in values.items()}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": not failed, "attempted": len(times),
              "failed": len(failed),
              "metrics": {k: {"value": v, "unit": u}
                          for k, (v, u) in values.items()},
              "device": device}
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in run.trace.top_ops],
            "idle_gaps": [list(x) for x in run.trace.idle_gaps]}
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in checks.items()}
    log(f"run total {time.perf_counter() - T_START:.1f}s")
    for k, v in checks.items():
        log(f"check {k}: {v:.6e} (limit {limits[k]:.1e})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
