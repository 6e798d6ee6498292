"""Token sampling for the serving engine.

The fused decode path samples ALL slots in one call with **vectorized
per-slot parameters** — a batch can mix greedy, temperature, top-k, and
top-p requests without leaving the single jitted kernel. Per-request
determinism: each row's PRNG key is derived from its own ``(seed, step)``
pair, so a request draws the same stream whether it runs alone or batched,
whatever slot it lands in, and across preemption/recompute (the step counter
is the request's cumulative token index).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def greedy(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def filter_logits(logits, top_k, top_p):
    """Vectorized per-row top-k / nucleus filtering.

    logits: (n, V) float; top_k: (n,) int32, 0 = disabled; top_p: (n,)
    float in (0, 1], 1 = disabled. Returns logits with filtered entries at
    ``-inf``. Nucleus keeps the *smallest* set of highest-probability tokens
    whose mass reaches ``top_p`` (the argmax always survives).

    One stable sort gives the descending values and their ids together.
    Both cuts keep a prefix of that order, ``m`` positions long, and the
    mask is built in id space from the last kept entry (value ``thr``, id
    ``last``): a stable sort puts equal values in ascending id order, so
    ``x > thr | (x == thr & id <= last)`` is exactly that prefix, ties
    included. No (n, V) gather or scatter.
    """
    n, v = logits.shape
    logits = logits.astype(jnp.float32)
    ids = jax.lax.broadcasted_iota(jnp.int32, (n, v), 1)
    neg_sorted, order = jax.lax.sort((-logits, ids), dimension=1,
                                     num_keys=1, is_stable=True)
    sorted_logits = -neg_sorted  # descending; negation is exact
    probs_sorted = jax.nn.softmax(sorted_logits, axis=-1)
    # exclusive cumulative mass: token t is kept iff the mass strictly above
    # it is still short of top_p  ->  smallest set with mass >= top_p
    cum_excl = jnp.cumsum(probs_sorted, axis=-1) - probs_sorted
    keep_p = cum_excl < top_p[:, None]
    # the first position the nucleus drops: a kept set that rounding made
    # other than a prefix can only shrink to its leading run, never grow
    m_p = jnp.where(keep_p.all(-1), v, jnp.argmax(~keep_p, axis=-1))
    m = jnp.minimum(m_p, jnp.where(top_k > 0, top_k, v))
    at = jnp.maximum(m - 1, 0)[:, None]
    thr = jnp.take_along_axis(sorted_logits, at, axis=-1)
    last = jnp.take_along_axis(order, at, axis=-1)
    keep = ((logits > thr) | ((logits == thr) & (ids <= last))) \
        & (m > 0)[:, None]
    return jnp.where(keep, logits, -jnp.inf)


def _greedy_rows(logits, *_):
    return greedy(logits)


def _draw(logits, seeds, steps, temperature, top_k, top_p):
    """Tokens of a batch in which some row samples: greedy rows get a dummy
    temperature of 1 so scaling stays finite, and their argmax."""
    sampled = temperature > 0
    safe_t = jnp.where(sampled, temperature, 1.0)[:, None]
    scaled = filter_logits(logits / safe_t, top_k, top_p)
    keys = jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.PRNGKey(s), c))(
            seeds, steps)
    drawn = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(sampled, drawn, greedy(logits))


def sample_batch(logits, seeds, steps, temperature, top_k, top_p):
    """One fused sampling step over all decode slots.

    logits: (n, V); seeds/steps: (n,) int32 per-request PRNG stream ids;
    temperature/top_k/top_p: (n,). Rows with ``temperature <= 0`` are greedy
    (argmax over raw logits). Temperature scaling happens BEFORE the
    top-k/top-p filters (vLLM/HF semantics: the nucleus is taken over the
    temperature-shaped distribution). Returns ``(tokens (n,) int32,
    logprobs (n,) float32)`` — logprobs are log p(token) under the raw
    (unfiltered, unscaled) distribution, for best-of-n ranking.

    A batch whose rows are all greedy takes the argmax alone: the filter's
    sort and the draw run only when some row samples (one program, both
    branches compiled).
    """
    logits = logits.astype(jnp.float32)
    # the branches take every input as an operand (no closure), so eager
    # calls reuse one traced cond
    tokens = jax.lax.cond((temperature > 0).any(), _draw, _greedy_rows,
                          logits, seeds, steps, temperature, top_k, top_p)
    logprobs = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), tokens[:, None], axis=-1)[:, 0]
    return tokens, logprobs


def sample(logits, key, *, temperature: float = 1.0, top_k: int = 0):
    """Scalar-parameter sampling (legacy path; the engine uses
    :func:`sample_batch`). logits: (B, V). temperature<=0 => greedy."""
    if temperature <= 0:
        return greedy(logits)
    logits = logits.astype(jnp.float32) / temperature
    if top_k:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
