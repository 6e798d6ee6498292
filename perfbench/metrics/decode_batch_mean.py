"""Requests given a decode token per decode iteration in the window, as the
client received them."""


def read(run):
    sizes = [len(ctxs) for ctxs in run.decode_polls]
    return sum(sizes) / len(sizes) if sizes else None
