"""Share of the traced window in which the engine had work and no op ran on
the device, in percent (time the harness waited for an arrival with the
engine empty is left out)."""


def read(run):
    if run.trace is None:
        return None
    work = run.trace.work_s()
    if work <= 0:
        return None
    idle = sum(b - a for a, b in run.trace.idle_with_work())
    return 100.0 * idle / work
