"""The routed experts' share of their roofline: the least time of the work
that the experts held here did in the traced window over the device time
of the grouped-matmul kernel that does it (three calls per MoE layer:
gate, up and down), in percent.

The work of each MoE program call is its ``engine``/``moe_route`` record:
2 x ``roofline.expert_params`` FLOPs per (token, expert) assignment to a
held expert, and 2 bytes x ``expert_params`` per held expert that a token
reached (each expert's weights read once). A call's least time is the
larger of the two over the chip's peaks; the calls of the traced
iterations are summed. The kernel is the Pallas grouped matmul of JAX's
``megablox``, ``gmm`` in the trace's op names."""

import re

from perfbench import roofline

KERNEL = re.compile(r"%?gmm(\.\d+)?(\s|=|$)")


def read(run):
    if run.trace is None or not roofline.is_moe(run.model):
        return None
    routes = [e.args for e in run.traced_events
              if e.cat == "engine" and e.name == "moe_route"]
    ops = [e for e in run.trace.ops.get(min(run.trace.modules), [])
           if KERNEL.match(e.name)]
    if not routes or not ops:
        return None
    per = 2.0 * roofline.expert_params(run.model)
    least = sum(roofline.least_time(per * sum(r["assigned"]),
                                    per * sum(r["hit"]), run.peaks)
                for r in routes)
    return 100.0 * least / sum(e.end - e.start for e in ops)
