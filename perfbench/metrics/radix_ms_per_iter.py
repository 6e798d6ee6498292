"""Radix prefix-cache milliseconds per engine iteration: the mean, over
the program's ``engine.iteration`` spans wholly inside the traced window,
of the time its ``prefix.match``, ``prefix.insert`` and ``prefix.evict``
spans cover."""

from perfbench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.mean_ms([
        spans.covered([k for k in kids if k.name.startswith(spans.PREFIX)])
        for _, kids in spans.iterations(run.trace)])
