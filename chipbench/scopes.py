"""Device time of the SpTTN programs by loop-nest term.

The program puts a named scope on every op a term lowers to:
``t<i>.lift``, ``t<i>.contract``, ``t<i>.reduce``, ``t<i>.scatter``,
``t<i>.dense``, ``t<i>.stage.<reduce|product|chain>`` (a generated Pallas
stage), and ``out`` for the output's materialization.  XLA keeps the scope
in each op's ``op_name`` metadata, and the profiler copies that into the
trace's event metadata as the ``tf_op`` stat, for example
``jit(spttn_mttkrp_m0)/t1.reduce/scatter-add:``.  A fused op carries the
metadata of its root, so it counts whole under the root's scope.

``jax.profiler.ProfileData`` does not expose event metadata stats, so
:func:`op_paths` reads them from the raw ``XSpace`` with a protobuf wire
reader of the few fields it needs.  Intervals, the window and the program
of each op come from ``trace.load``'s events, as in ``trace.reduce``.
"""
from __future__ import annotations

import bisect
import re
import time
from collections import defaultdict
from pathlib import Path

from chipbench import trace

TRACE_DIR = Path(__file__).resolve().parent / ".cache" / "trace"
SCOPE = re.compile(r"t\d+\.[a-z.]+|out")
PROGRAM_ID = re.compile(r"\((\d+)\)$")
NO_SCOPE = "(no scope)"


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one protobuf message: an
    int for a varint or fixed-width field, a memoryview for a
    length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf) -> tuple[int, object]:
    """An ``XStat`` as ``(metadata id, value)``: ``str_value`` as text,
    ``ref_value`` as ``("ref", id)``, an integer as int."""
    mid, value = 0, None
    for f, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 5:
            value = _text(v)
        elif f == 7:
            value = ("ref", v)
        elif f in (3, 4):
            value = v
    return mid, value


def op_paths(path: str) -> dict[tuple[str, str], str]:
    """``(program id, op name) -> tf_op`` for every op of every device
    plane of the ``.xplane.pb`` at ``path``.  The op name is the event's
    name as ``trace.load`` gives it; the program id is the number in the
    name of the ``XLA Modules`` event that runs it."""
    data = memoryview(Path(path).read_bytes())
    out: dict[tuple[str, str], str] = {}
    for f, plane in _fields(data):                       # XSpace.planes
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:                                  # XPlane.name
                name = _text(v)
            elif pf == 4:                                # event_metadata
                events.append(dict(_fields(v)).get(2))
            elif pf == 5:                                # stat_metadata
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = _text(meta.get(2, b""))
        if not name.startswith("/device:"):
            continue
        ids = {n: k for k, n in stat_names.items()}
        tf_op, program = ids.get("tf_op"), ids.get("program_id")
        for ev in events:
            if ev is None:
                continue
            op, stats = "", {}
            for ef, v in _fields(ev):
                if ef == 2:                              # name
                    op = _text(v)
                elif ef == 5:                            # stats
                    mid, value = _stat(v)
                    stats[mid] = value
            value = stats.get(tf_op)
            if isinstance(value, tuple):
                value = stat_names.get(value[1])
            if value and program in stats:
                out[(str(stats[program]), op)] = value
    return out


def scope_of(tf_op: str | None) -> str | None:
    """The innermost term scope in a ``tf_op`` path, or ``None``."""
    if not tf_op:
        return None
    parts = tf_op.rsplit(":", 1)[0].split("/")
    for part in reversed(parts):
        if SCOPE.fullmatch(part):
            return part
    return None


def kind_of(scope: str) -> str:
    """``t3.stage.reduce`` -> ``stage.reduce``; ``out`` -> ``out``."""
    return scope.split(".", 1)[1] if scope != "out" else scope


def term_seconds(tr: trace.Trace, paths) -> dict[str, dict[str, float]]:
    """Device seconds inside the window, per program and per scope
    (``NO_SCOPE`` for ops without one), averaged over the devices."""
    windows = [e for e in tr.host if e.name == trace.WINDOW]
    if not windows:
        raise ValueError(f"no host annotation {trace.WINDOW!r} in the trace")
    lo, hi = windows[0].start, windows[0].end
    devices = [d for d in sorted(tr.devices)
               if tr.devices[d].get(trace.OPS_LINE)]
    out: dict[str, dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for dev in devices:
        lines = tr.devices[dev]
        modules = sorted(lines.get(trace.MODULES_LINE, []),
                         key=lambda e: e.start)
        starts = [m.start for m in modules]
        for e in lines[trace.OPS_LINE]:
            a, b = max(e.start, lo), min(e.end, hi)
            if b <= a:
                continue
            k = bisect.bisect_right(starts, e.start) - 1
            if k < 0 or e.start >= modules[k].end:
                continue
            module = modules[k].name
            pid = PROGRAM_ID.search(module)
            tf_op = paths.get((pid.group(1), e.name)) if pid else None
            scope = scope_of(tf_op) or NO_SCOPE
            out[trace.program_name(module)][scope] += \
                (b - a) * 1e-9 / len(devices)
    return {p: dict(s) for p, s in out.items()}


def trace_file() -> Path | None:
    """The traced window's ``.xplane.pb``, where ``run.py`` writes it."""
    files = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None


def spttn_scopes(run) -> dict[str, dict[str, float]] | None:
    """Per-scope device seconds of the ``spttn_*`` programs in the traced
    window (read once per run and logged), or ``None`` where there is no
    device trace or no op of those programs carries a term scope."""
    if "spttn_scopes" in run.__dict__:
        return run.__dict__["spttn_scopes"]
    out = None
    path = trace_file() if run.trace is not None else None
    if path is not None:
        t0 = time.perf_counter()
        seconds = term_seconds(trace.load(str(path)), op_paths(str(path)))
        run.log(f"scopes: trace read again in "
                f"{time.perf_counter() - t0:.2f}s")
        out = {p: s for p, s in seconds.items() if p.startswith("spttn_")}
        for p, s in sorted(out.items()):
            run.log(f"scopes {p}: " + ", ".join(
                f"{k} {v:.6f}s" for k, v in sorted(s.items())))
        total = sum(v for s in out.values() for v in s.values())
        bare = sum(s.get(NO_SCOPE, 0.0) for s in out.values())
        if total > 0:
            run.log(f"scopes: {100 * bare / total:.4f}% of spttn device "
                    f"time ({total:.4f}s) carries no scope")
        if total == 0 or bare == total:
            run.log("scopes: no op of the spttn programs carries a term "
                    "scope (the program sets none, or its executables "
                    "came from a compile cache written without them)")
            out = None
    run.__dict__["spttn_scopes"] = out
    return out


def spttn_share(run, kinds) -> float | None:
    """Share (%) of the ``spttn_*`` programs' op time under a scope of
    one of ``kinds``."""
    scopes = spttn_scopes(run)
    if scopes is None:
        return None
    total = sum(v for s in scopes.values() for v in s.values())
    part = sum(v for s in scopes.values() for k, v in s.items()
               if k != NO_SCOPE and kind_of(k) in kinds)
    return 100.0 * part / total
