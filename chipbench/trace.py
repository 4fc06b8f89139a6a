"""Reduction of a JAX profiler trace to the harness's device numbers.

``load`` reads an ``.xplane.pb`` with JAX's own ``ProfileData`` into plain
events; ``reduce`` turns them into a :class:`Summary` over the traced
window, which the harness marks with a host annotation named ``window``:

* busy time: the union of the device-op intervals inside the window, per
  device, averaged over the devices;
* per program: device seconds and calls of each XLA module, by the name of
  the function the harness jitted (``jit_<name>`` in the trace);
* the device ops that took the most time, by op and result shape;
* idle gaps: the parts of the window that no device op covers, each named
  by the innermost harness span open on the host at its midpoint.

Device and host events share one clock in the profiler's output, so host
spans and device intervals compare directly.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"
NO_SPAN = "(no span)"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float      # ns
    end: float        # ns


@dataclasses.dataclass
class Trace:
    devices: dict[str, dict[str, list[Event]]]   # plane -> line -> events
    host: list[Event]                             # every host-plane event


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                        # mean over devices
    programs: dict[str, tuple[float, int]]   # name -> (device s, calls)
    top_ops: list[tuple[str, float]]     # (op name, device s), descending
    idle_gaps: list[tuple[str, float]]   # (host span, idle s), descending
    devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict[str, dict[str, list[Event]]] = {}
    host: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            devices[plane.name] = {
                line.name: [Event(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                            for e in line.events]
                for line in plane.lines}
        elif plane.name.startswith("/host:"):
            host.extend(Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for line in plane.lines for e in line.events)
    return Trace(devices=devices, host=host)


def union(intervals) -> list[tuple[float, float]]:
    """Sorted disjoint union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(events, lo, hi):
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def program_name(module: str) -> str:
    """``jit_spttn_mttkrp_m0(12)`` -> ``spttn_mttkrp_m0``."""
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def op_label(op: str) -> str:
    """``%fusion.2 = f32[9,16]{0,1} fusion(...), kind=...`` ->
    ``fusion.2 = f32[9,16]{0,1}``: the op and its result, without its
    operands."""
    m = re.match(r"%?(\S+ = \S+)", op)
    return m.group(1) if m else op


def innermost(spans: list[Event], t: float) -> str:
    """Name of the innermost span (latest start) that contains ``t``."""
    best = None
    for s in spans:
        if s.start <= t < s.end and (best is None or s.start >= best.start):
            best = s
    return NO_SPAN if best is None else best.name


def reduce(trace: Trace, span_names, top: int = 10) -> Summary | None:
    """Summary of the window; ``span_names`` are the harness's host spans
    that idle gaps may be named by.  ``None`` when no device ran an op
    (the CPU backend has no device plane)."""
    windows = [e for e in trace.host if e.name == WINDOW]
    if not windows:
        raise ValueError(f"no host annotation {WINDOW!r} in the trace")
    lo, hi = windows[0].start, windows[0].end
    spans = [e for e in trace.host if e.name in set(span_names)
             and e.end > lo and e.start < hi]
    busy_total = 0.0
    programs: dict[str, list[float]] = defaultdict(lambda: [0.0, 0])
    ops: dict[str, float] = defaultdict(float)
    gaps: dict[str, float] = defaultdict(float)
    devices = [d for d in sorted(trace.devices)
               if trace.devices[d].get(OPS_LINE)]
    if not devices:
        return None
    for n, dev in enumerate(devices):
        lines = trace.devices[dev]
        busy = union(_clip(lines[OPS_LINE], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        for e in lines[OPS_LINE]:
            a, b = max(e.start, lo), min(e.end, hi)
            if b > a:
                ops[op_label(e.name)] += (b - a) / len(devices)
        for e in lines.get(MODULES_LINE, []):
            a, b = max(e.start, lo), min(e.end, hi)
            if b > a:
                p = programs[program_name(e.name)]
                p[0] += (b - a) / len(devices)
                p[1] += 1 if n == 0 else 0
        if n == 0:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps[innermost(spans, (a + b) / 2)] += b - a
    ns = 1e-9
    return Summary(
        window_s=(hi - lo) * ns,
        busy_s=busy_total / len(devices) * ns,
        programs={k: (v[0] * ns, int(v[1])) for k, v in programs.items()},
        top_ops=sorted(((k, v * ns) for k, v in ops.items()),
                       key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(((k, v * ns) for k, v in gaps.items()),
                         key=lambda kv: -kv[1])[:top],
        devices=len(devices))
