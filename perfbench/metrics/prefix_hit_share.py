"""Share of the prompt tokens admitted in the window that the radix prefix
cache served: the window's change of the cache's cumulative
``hit_tokens`` over that of ``lookup_tokens``, in percent."""


def read(run):
    c = run.counters
    if not c.get("prefix_lookup_tokens"):
        return None
    return 100.0 * c["prefix_hit_tokens"] / c["prefix_lookup_tokens"]
