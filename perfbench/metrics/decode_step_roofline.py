"""The decode step's share of its roofline: the least time the chip needs
for the work of each decode run (``roofline.decode_flops`` and
``decode_bytes`` over the active sequences' real contexts and, for MoE,
the step's routing record) over the run's device time, summed over the
traced window, in percent. Runs are paired in order with the decode
iterations the client saw in the same window."""

from perfbench import roofline
from perfbench.metrics.decode_step_ms import PROGRAM


def read(run):
    if run.trace is None or not run.traced_decodes:
        return None
    runs = run.trace.program_runs(PROGRAM)
    route = roofline.routes(run.model, run.traced_events, "decode",
                            len(run.traced_decodes))
    if len(runs) != len(run.traced_decodes) or route is None:
        return None
    least = sum(roofline.least_time(roofline.decode_flops(run.model, c, r),
                                    roofline.decode_bytes(run.model, c, r),
                                    run.peaks)
                for c, r in zip(run.traced_decodes, route))
    return 100.0 * least / sum(e.end - e.start for e in runs)
