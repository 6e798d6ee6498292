"""Tokens that each read of a held expert's weights serves in decode: the
window's change of the engine's ``moe_decode_assigned`` ((token, expert)
assignments to experts held here) over that of ``moe_decode_hit`` (held
experts that some token reached), summed over the MoE layers of every
decode step."""


def read(run):
    c = run.counters
    if not c.get("moe_decode_hit"):
        return None
    return c["moe_decode_assigned"] / c["moe_decode_hit"]
