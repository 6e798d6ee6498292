"""Model FLOP utilization of the engine's steps: the model FLOPs of every
prefill chunk and decode step in the traced window (``roofline``) over
their programs' device time times the chip's bf16 peak, in percent."""

from perfbench import roofline
from perfbench.metrics.decode_step_ms import PROGRAM as DECODE
from perfbench.metrics.prefill_ms_per_ktok import PROGRAM as PREFILL


def read(run):
    if run.trace is None:
        return None
    dec = run.trace.program_runs(DECODE)
    pre = run.trace.program_runs(PREFILL)
    if len(dec) != len(run.traced_decodes) or \
            len(pre) != len(run.traced_chunks) or not (dec or pre):
        return None
    flops = sum(roofline.decode_flops(run.model, c)
                for c in run.traced_decodes) + \
        sum(roofline.prefill_flops(run.model, s, n)
            for s, n in run.traced_chunks)
    secs = sum(e.end - e.start for e in dec + pre)
    return 100.0 * flops / (secs * run.peaks["bf16_flops_per_s"])
