"""Median wait in the scheduler's queue of the requests sent in the window:
the program's first admission of each request (its telemetry's
``sched/admit`` event, on the harness's clock) less the time it was due."""

from perfbench.stats import percentile


def read(run):
    waits = [run.admits[r.rid] - r.due for r in run.requests
             if r.window and r.rid in run.admits]
    return percentile(waits, 50) if waits else None
