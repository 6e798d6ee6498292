"""The one traffic generator: every mix under ``traffic/`` is a JSON file of
parameters that this module reads.

Lengths follow the serving simulator's distributions (lognormal prompt and
output lengths, exponential inter-arrival gaps: ``serving/simulator.py``
``make_workload``), but are drawn as stratified quantiles instead of
independent samples, and put in one fixed order: every seed sends the same
lengths at the same times (and, for documents, the same popularity ranks)
with other token ids. So two seeds do the same work, and the spread
between runs is the system's, not the sampler's.

A mix file holds:

``arrivals``  ``{"kind": "poisson", "rate_per_s": r}`` (open loop: each
              request is due at a fixed time, whatever the server does) or
              ``{"kind": "closed", "clients": c}`` (each client sends its
              next request when its last one has finished)
``lead_in_s`` seconds of the same traffic before the measured window
``drain_s``   how long after the window a request sent in it may take
``prompt``    ``{"kind": "unique", "len": <dist>}`` or ``{"kind":
              "document", "corpus": {"docs": n, "len": <dist>, "zipf_s":
              s}, "question": <dist>}`` (a corpus document, picked by a
              Zipf law over popularity ranks, then a unique question)
``output``    <dist> of the number of tokens generated (greedy, no eos)
``check``     ``{"min_tokens": t, "max_requests": m}``: the sample of
              finished requests the reference re-computes
``block``     closed loop only: lengths repeat their full quantile set
              every ``block`` requests

A <dist> is ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
"max": b}`` or ``{"dist": "uniform", "min": a, "max": b}``.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, Iterator, List, Optional

import numpy as np

# token 0 starts every warm-up prompt and no traffic prompt, so no request
# of a mix ever matches a warm-up prompt in the prefix cache
WARMUP_TOKEN = 0
# the order of lengths, gaps and document ranks, the same for every seed
_ORDER_SEED = 12345


@dataclasses.dataclass
class Spec:
    """One request: its prompt, its output length and (open loop) when it
    is due, in seconds after the start of the traffic."""

    prompt: List[int]
    max_new: int
    due: Optional[float] = None
    doc: Optional[int] = None


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles ``(i + 0.5) / n`` of ``dist``, rounded
    to whole tokens and clipped to its bounds."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = lo + q * (hi - lo)
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def dist_max(dist: Dict) -> int:
    return int(dist["max"])


def max_prompt_len(mix: Dict) -> int:
    p = mix["prompt"]
    if p["kind"] == "unique":
        return dist_max(p["len"])
    return dist_max(p["corpus"]["len"]) + dist_max(p["question"])


def max_context(mix: Dict) -> int:
    return max_prompt_len(mix) + dist_max(mix["output"])


def _gaps(rate: float, n: int, span: float,
          rng: np.random.Generator) -> np.ndarray:
    """Arrival times of ``n`` requests in ``[0, span)``: stratified
    exponential gaps of mean about ``1/rate``, in ``rng``'s order, scaled so
    that they add up to the span (the first request is due at 0, the last
    gap closes the span)."""
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    return (np.cumsum(gaps) - gaps) * (span / gaps.sum())


class Traffic:
    """The requests of one mix under one seed. ``vocab`` bounds the ids."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)           # token ids
        self.order = np.random.default_rng(_ORDER_SEED)  # sizes, times
        # first tokens drawn without replacement: no two prompts (nor two
        # documents) share a prefix unless the mix says so
        self._firsts = iter(self.rng.permutation(np.arange(1, vocab)))
        self.docs: List[List[int]] = []
        p = mix["prompt"]
        if p["kind"] == "document":
            c = p["corpus"]
            lens = quantiles(c["len"], c["docs"])
            lens = lens[self.order.permutation(c["docs"])]
            self.docs = [self._tokens(int(n)) for n in lens]
        elif p["kind"] != "unique":
            raise ValueError(f"unknown prompt kind {p['kind']!r}")

    def _tokens(self, n: int) -> List[int]:
        first = int(next(self._firsts))
        return [first] + self.rng.integers(1, self.vocab, n - 1).tolist()

    def _zipf_ranks(self, n: int) -> np.ndarray:
        c = self.mix["prompt"]["corpus"]
        w = np.arange(1, c["docs"] + 1, dtype=np.float64) ** -c["zipf_s"]
        cdf = np.cumsum(w / w.sum())
        q = (np.arange(n) + 0.5) / n
        return np.minimum(np.searchsorted(cdf, q), c["docs"] - 1)

    def batch(self, n: int) -> List[Spec]:
        """``n`` requests whose lengths are the full stratified set of
        size ``n``, in the fixed order."""
        p = self.mix["prompt"]
        outs = self.order.permutation(quantiles(self.mix["output"], n))
        specs = []
        if p["kind"] == "unique":
            plens = self.order.permutation(quantiles(p["len"], n))
            for pl, o in zip(plens, outs):
                specs.append(Spec(self._tokens(int(pl)), int(o)))
        else:
            ranks = self.order.permutation(self._zipf_ranks(n))
            qlens = self.order.permutation(quantiles(p["question"], n))
            for r, ql, o in zip(ranks, qlens, outs):
                prompt = self.docs[r] + self._tokens(int(ql))
                specs.append(Spec(prompt, int(o), doc=int(r)))
        return specs

    # -- open loop ---------------------------------------------------------------

    def open_loop(self, window_s: float) -> List[Spec]:
        """Every request of an open-loop run: the lead-in's, due in
        ``[0, lead_in_s)``, then the window's, due in ``[lead_in_s,
        lead_in_s + window_s)``. Each part holds the full stratified set
        of its size."""
        rate = self.mix["arrivals"]["rate_per_s"]
        lead = float(self.mix["lead_in_s"])
        out = []
        for start, span in ((0.0, lead), (lead, float(window_s))):
            n = int(round(rate * span))
            specs = self.batch(n)
            for s, t in zip(specs, _gaps(rate, n, span, self.order)):
                s.due = start + float(t)
            out.extend(specs)
        return out

    # -- closed loop -------------------------------------------------------------

    def stream(self) -> Iterator[Spec]:
        """An endless closed-loop stream, ``block`` requests at a time."""
        b = int(self.mix.get("block", 32))
        while True:
            yield from self.batch(b)


def is_open_loop(mix: Dict) -> bool:
    return mix["arrivals"]["kind"] == "poisson"
