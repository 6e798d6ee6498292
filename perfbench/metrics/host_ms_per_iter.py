"""Host milliseconds per engine iteration: the mean, over the program's
``engine.iteration`` spans wholly inside the traced window, of each span's
duration less the time its ``engine.sync`` spans (the host waiting for the
device) cover."""

from perfbench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.mean_ms([
        (it.end - it.start) -
        spans.covered([k for k in kids if k.name == spans.SYNC])
        for it, kids in spans.iterations(run.trace)])
