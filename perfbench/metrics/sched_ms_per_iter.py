"""Scheduler milliseconds per engine iteration: the mean, over the
program's ``engine.iteration`` spans wholly inside the traced window, of
the time its ``sched.schedule`` and ``sched.complete`` spans cover, less
the program spans nested in them (``prefix.*``, ``engine.sync``): the
scheduler's self time."""

from perfbench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.mean_ms([
        spans.self_time(
            [k for k in kids if k.name in spans.SCHED],
            [k for k in kids if k.name.startswith(spans.PREFIX)
             or k.name == spans.SYNC])
        for _, kids in spans.iterations(run.trace)])
