"""Roofline share of one kernel kind over the traced window, shared by the
``<kernel>_roofline`` readers."""
from chipbench import work


def kernel_roofline(run, kind: str):
    """``100 * (least time the chip could take) / (device time)``, summed
    over every call of the kind's programs in the window; ``None`` where
    the trace holds none of them."""
    if run.trace is None or run.job is None:
        return None
    least = measured = 0.0
    bounds = set()
    peak = None
    for k in run.job.kernels_info:
        if k["kind"] != kind or k["name"] not in run.trace.programs:
            continue
        seconds, calls = run.trace.programs[k["name"]]
        if calls == 0 or seconds <= 0:
            continue
        peak = peak or work.peaks(run.device_kind)
        ops, nbytes = work.work(kind, k["dims"], k["ranks"],
                                run.levels(k["mode"]))
        share, bound = work.roofline(ops * calls, nbytes * calls, seconds,
                                     peak)
        least += share * seconds / 100.0
        measured += seconds
        bounds.add(bound)
    if measured == 0:
        return None
    run.log(f"{kind}_roofline: {'/'.join(sorted(bounds))}-bound")
    return 100.0 * least / measured
