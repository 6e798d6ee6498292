"""Share of the block table that the decode step has to read: the
window's change of the engine's ``decode_live_pages`` (the pages inside
each active slot's context and window, what the paged-attention kernel
copies) over that of ``decode_table_pages`` (every slot's whole block
table, what a gather of the table reads), in percent."""


def read(run):
    c = run.counters
    if not c.get("decode_table_pages"):
        return None
    return 100.0 * c["decode_live_pages"] / c["decode_table_pages"]
