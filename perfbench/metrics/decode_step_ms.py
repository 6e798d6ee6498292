"""Device milliseconds per run of the decode program, from the trace."""

PROGRAM = "_decode_fn"


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.program_runs(PROGRAM)
    if not runs:
        return None
    return 1e3 * sum(e.end - e.start for e in runs) / len(runs)
