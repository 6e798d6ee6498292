"""Device milliseconds of the prefill program per thousand prefill tokens
in the traced window: its runs in the profiler trace over the tokens of
the program's ``engine/chunk`` telemetry events of the same iterations."""

PROGRAM = "_prefill_chunk_fn"


def read(run):
    if run.trace is None or not run.traced_chunks:
        return None
    runs = run.trace.program_runs(PROGRAM)
    if len(runs) != len(run.traced_chunks):
        return None
    tokens = sum(length for _, length in run.traced_chunks)
    return 1e3 * sum(e.end - e.start for e in runs) / (tokens / 1e3)
