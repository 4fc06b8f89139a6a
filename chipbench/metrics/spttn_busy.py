"""Share (%) of the device's busy time spent inside the SpTTN programs
(``spttn_*``, the engine and its generated stages), as against the dense
update and the rest."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    spttn = sum(s for name, (s, _) in run.trace.programs.items()
                if name.startswith("spttn_"))
    if spttn == 0:
        return None
    return 100.0 * spttn / run.trace.busy_s
