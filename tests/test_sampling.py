"""Vectorized per-slot sampling: top-k / top-p filter invariants, greedy
exactness, per-request stream determinism (batch- and slot-independent),
and equality with the earlier rank-scatter filter kept below as the
reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.models import sampling


def _rows(n, v, seed=0):
    rng = np.random.default_rng(seed)
    # distinct values so top-k set membership is unambiguous
    x = rng.normal(size=(n, v)).astype(np.float32)
    x += np.linspace(0, 1e-3, v)[None, :] * rng.random((n, 1))
    return jnp.asarray(x)


def _kept(filtered):
    return np.isfinite(np.asarray(filtered))


# -- top-k ---------------------------------------------------------------------

def test_top_k_keeps_exactly_k_largest():
    logits = _rows(3, 64)
    ks = jnp.asarray([5, 1, 0], jnp.int32)  # 0 = disabled
    f = sampling.filter_logits(logits, ks, jnp.ones(3, jnp.float32))
    kept = _kept(f)
    assert kept[0].sum() == 5 and kept[1].sum() == 1 and kept[2].sum() == 64
    # the kept entries are precisely the k largest
    row = np.asarray(logits[0])
    assert set(np.where(kept[0])[0]) == set(np.argsort(-row)[:5])
    assert np.where(kept[1])[0][0] == np.argmax(np.asarray(logits[1]))


def test_top_p_keeps_smallest_set_reaching_mass():
    logits = _rows(4, 64, seed=1)
    ps = jnp.asarray([0.1, 0.5, 0.9, 1.0], jnp.float32)
    f = sampling.filter_logits(logits, jnp.zeros(4, jnp.int32), ps)
    kept = _kept(f)
    probs = np.array(jnp.exp(jnp.array(logits) -
                             jnp.max(logits, -1, keepdims=True)))
    probs /= probs.sum(-1, keepdims=True)
    for i, p in enumerate((0.1, 0.5, 0.9)):
        mass = probs[i][kept[i]].sum()
        # kept mass reaches p, and dropping the smallest kept token would
        # fall short of p: the nucleus is the *smallest* such set
        assert mass >= p - 1e-6
        assert mass - probs[i][kept[i]].min() < p + 1e-6
        # argmax always survives
        assert kept[i][np.argmax(probs[i])]
    assert kept[3].all()  # top_p = 1.0 disables the filter


def test_top_k_and_top_p_compose():
    logits = _rows(1, 32, seed=2)
    f = sampling.filter_logits(logits, jnp.asarray([4], jnp.int32),
                               jnp.asarray([0.99], jnp.float32))
    assert _kept(f)[0].sum() <= 4


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_filter_invariants_property(seed):
    rng = np.random.default_rng(seed)
    n, v = 4, 48
    logits = jnp.asarray(rng.normal(size=(n, v)).astype(np.float32))
    top_k = jnp.asarray(rng.integers(0, v + 1, n), jnp.int32)
    top_p = jnp.asarray(rng.uniform(0.05, 1.0, n).astype(np.float32))
    kept = _kept(sampling.filter_logits(logits, top_k, top_p))
    arg = np.argmax(np.asarray(logits), -1)
    for i in range(n):
        assert kept[i].any() and kept[i][arg[i]]
        if int(top_k[i]) > 0:
            assert kept[i].sum() <= int(top_k[i])


# -- sample_batch --------------------------------------------------------------

def _sample(logits, seeds, steps, temp, top_k=None, top_p=None):
    n = logits.shape[0]
    return sampling.sample_batch(
        logits, jnp.asarray(seeds, jnp.int32), jnp.asarray(steps, jnp.int32),
        jnp.asarray(temp, jnp.float32),
        jnp.asarray(top_k if top_k is not None else [0] * n, jnp.int32),
        jnp.asarray(top_p if top_p is not None else [1.0] * n, jnp.float32))


def test_greedy_rows_are_exact_argmax():
    logits = _rows(4, 64, seed=3)
    toks, lps = _sample(logits, [0] * 4, [0] * 4, [0.0, 0.0, 1.0, 0.0])
    arg = np.argmax(np.asarray(logits), -1)
    assert list(np.asarray(toks)[[0, 1, 3]]) == list(arg[[0, 1, 3]])
    # reported logprob is log-softmax of the chosen token
    lsm = np.asarray(jnp.log(jnp.exp(logits[0] - jnp.max(logits[0])) /
                             jnp.sum(jnp.exp(logits[0] - jnp.max(logits[0])))))
    assert np.isclose(float(lps[0]), lsm[arg[0]], atol=1e-5)


def test_sampled_token_respects_filters():
    logits = _rows(8, 64, seed=4)
    # top_k=1 forces the argmax even at high temperature
    toks, _ = _sample(logits, list(range(8)), [0] * 8, [2.0] * 8,
                      top_k=[1] * 8)
    assert list(np.asarray(toks)) == list(np.argmax(np.asarray(logits), -1))


def test_stream_is_deterministic_and_batch_independent():
    logits = _rows(6, 64, seed=5)
    a = _sample(logits, [7] * 6, list(range(6)), [1.0] * 6)[0]
    b = _sample(logits, [7] * 6, list(range(6)), [1.0] * 6)[0]
    assert list(np.asarray(a)) == list(np.asarray(b))
    # row 2 sampled alone (same seed/step) draws the same token as in-batch
    alone = _sample(logits[2:3], [7], [2], [1.0])[0]
    assert int(alone[0]) == int(a[2])


def test_different_steps_decorrelate():
    logits = jnp.zeros((32, 128), jnp.float32)  # uniform: pure randomness
    toks, _ = _sample(logits, [11] * 32, list(range(32)), [1.0] * 32)
    assert len(set(np.asarray(toks).tolist())) > 8


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_temperature_mass_property(seed):
    """Sampled tokens at low temperature concentrate on higher-probability
    tokens than at high temperature (distributional sanity via many seeds)."""
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(1, 32)).astype(np.float32) * 3)
    lo = [int(_sample(logits, [s], [0], [0.3])[0][0]) for s in range(40)]
    hi = [int(_sample(logits, [s], [0], [3.0])[0][0]) for s in range(40)]
    p = np.array(jnp.exp(logits[0] - jnp.max(logits[0])))  # writable copy
    p /= p.sum()
    assert np.mean(p[lo]) >= np.mean(p[hi]) - 1e-3


# -- the rank-scatter filter it replaced -----------------------------------------
# Frozen copies of the earlier ``filter_logits`` / ``sample_batch``: an argsort,
# a full-vocabulary rank scatter and two full-vocabulary gathers, on every row
# whatever its temperature. The sampler must keep their sets, tokens and
# logprobs exactly.


def _frozen_filter_logits(logits, top_k, top_p):
    n, v = logits.shape
    logits = logits.astype(jnp.float32)
    order = jnp.argsort(-logits, axis=-1)
    ranks = jnp.zeros((n, v), jnp.int32).at[
        jnp.arange(n)[:, None], order].set(jnp.arange(v, dtype=jnp.int32))
    k_eff = jnp.where(top_k > 0, top_k, v)
    keep_k = ranks < k_eff[:, None]
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    probs_sorted = jax.nn.softmax(sorted_logits, axis=-1)
    cum_excl = jnp.cumsum(probs_sorted, axis=-1) - probs_sorted
    keep_sorted = cum_excl < top_p[:, None]
    keep_p = jnp.take_along_axis(keep_sorted, ranks, axis=-1)
    return jnp.where(keep_k & keep_p, logits, -jnp.inf)


def _frozen_sample_batch(logits, seeds, steps, temperature, top_k, top_p):
    logits = logits.astype(jnp.float32)
    greedy_tok = sampling.greedy(logits)
    safe_t = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    scaled = _frozen_filter_logits(logits / safe_t, top_k, top_p)
    keys = jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.PRNGKey(s), c))(
            seeds, steps)
    drawn = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    tokens = jnp.where(temperature > 0, drawn, greedy_tok)
    logprobs = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), tokens[:, None], axis=-1)[:, 0]
    return tokens, logprobs


_filter = jax.jit(sampling.filter_logits)
_frozen_filter = jax.jit(_frozen_filter_logits)
_sample_new = jax.jit(sampling.sample_batch)
_sample_frozen = jax.jit(_frozen_sample_batch)


def _tied_rows(rng, n, v, bf16=True, scale=3.0):
    """Rows rounded through bf16 (many exact ties) or left in float32."""
    x = jnp.asarray(rng.normal(size=(n, v)).astype(np.float32) * scale)
    return x.astype(jnp.bfloat16).astype(jnp.float32) if bf16 else x


def _assert_prefix_of_stable_sort(logits, kept):
    """Each row's kept set is a leading run of its stable descending sort
    (equal values in ascending id order)."""
    for row, k in zip(np.asarray(logits), kept):
        order = np.argsort(-row, kind="stable")
        in_order = k[order]
        m = int(in_order.sum())
        assert in_order[:m].all() and not in_order[m:].any()


@pytest.mark.parametrize("case", [
    "bf16_ties", "float32", "top_k_0_1", "top_p_1", "top_k_past_v",
    "top_p_tiny", "top_p_0", "flat_rows", "neg_inf_entries"])
def test_filter_keeps_the_frozen_set(case):
    rng = np.random.default_rng(sum(map(ord, case)))
    n, v = 8, 257
    logits = _tied_rows(rng, n, v, bf16=case != "float32")
    top_k = rng.integers(0, 40, n).astype(np.int32)
    top_p = rng.uniform(0.05, 1.0, n).astype(np.float32)
    if case == "top_k_0_1":
        top_k = np.array([0, 1] * (n // 2), np.int32)
    if case == "top_p_1":
        top_p = np.ones(n, np.float32)
        top_k[::2] = 0
    if case == "top_k_past_v":
        top_k[:] = v + 5
    if case == "top_p_tiny":
        top_p = np.full(n, 1e-6, np.float32)
        top_k[:] = 0
    if case == "top_p_0":  # keeps nothing, as the frozen filter does
        top_p[::2] = 0.0
    if case == "flat_rows":  # every entry of a row ties with every other
        logits = jnp.zeros((n, v), jnp.float32).at[1::2].set(0.5)
    if case == "neg_inf_entries":  # a masked vocabulary tail
        logits = logits.at[:, v - 60:].set(-jnp.inf)
        top_p[:2] = 1.0
        top_k[:2] = 0
    top_k, top_p = jnp.asarray(top_k), jnp.asarray(top_p)
    want = _frozen_filter(logits, top_k, top_p)
    got = _filter(logits, top_k, top_p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _assert_prefix_of_stable_sort(logits, _kept(want))


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), st.booleans())
def test_kept_set_is_a_stable_sort_prefix_property(seed, bf16):
    """The frozen filter keeps a prefix of the stable descending order, so
    the threshold mask keeps exactly its set."""
    rng = np.random.default_rng(seed)
    n, v = 8, 257
    logits = _tied_rows(rng, n, v, bf16=bf16, scale=rng.uniform(0.5, 8.0))
    top_k = jnp.asarray(rng.integers(0, v + 1, n) * rng.integers(0, 2, n),
                        jnp.int32)
    top_p = jnp.asarray(rng.uniform(0.01, 1.0, n).astype(np.float32))
    want = _frozen_filter(logits, top_k, top_p)
    _assert_prefix_of_stable_sort(logits, _kept(want))
    np.testing.assert_array_equal(
        np.asarray(_filter(logits, top_k, top_p)), np.asarray(want))


def _params(kind, n, rng):
    temp = rng.uniform(0.3, 2.0, n).astype(np.float32)
    if kind == "greedy":
        temp[:] = 0.0
    elif kind == "mixed":
        temp[::2] = 0.0
    top_k = (rng.integers(0, 20, n) * rng.integers(0, 2, n)).astype(np.int32)
    top_p = np.where(rng.random(n) < 0.5, 1.0,
                     rng.uniform(0.2, 0.95, n)).astype(np.float32)
    seeds = rng.integers(0, 2**31 - 1, n).astype(np.int32)
    steps = rng.integers(0, 5000, n).astype(np.int32)
    return [jnp.asarray(a) for a in (seeds, steps, temp, top_k, top_p)]


@pytest.mark.parametrize("bf16", [True, False])
@pytest.mark.parametrize("kind", ["greedy", "sampled", "mixed"])
def test_sample_batch_matches_frozen(kind, bf16):
    """Identical tokens and logprobs, bit for bit, for fixed (seed, step)
    streams, whether the batch takes the greedy branch or the sort."""
    rng = np.random.default_rng([len(kind), sum(map(ord, kind)), bf16])
    n, v = 8, 257
    for _ in range(3):
        logits = _tied_rows(rng, n, v, bf16=bf16)
        args = _params(kind, n, rng)
        toks, lps = _sample_new(logits, *args)
        want_toks, want_lps = _sample_frozen(logits, *args)
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(want_toks))
        np.testing.assert_array_equal(np.asarray(lps), np.asarray(want_lps))


def test_one_sampled_row_leaves_greedy_rows_alone():
    """A batch that takes the sorted branch for one row gives its greedy
    rows the tokens and logprobs of an all-greedy batch."""
    rng = np.random.default_rng(17)
    n, v = 8, 257
    logits = _tied_rows(rng, n, v)
    seeds, steps, _, top_k, top_p = _params("greedy", n, rng)
    greedy_temp = jnp.zeros(n, jnp.float32)
    one_temp = greedy_temp.at[3].set(1.3)
    g_toks, g_lps = _sample_new(logits, seeds, steps, greedy_temp, top_k,
                                top_p)
    m_toks, m_lps = _sample_new(logits, seeds, steps, one_temp, top_k, top_p)
    rest = np.arange(n) != 3
    np.testing.assert_array_equal(np.asarray(m_toks)[rest],
                                  np.asarray(g_toks)[rest])
    np.testing.assert_array_equal(np.asarray(m_lps)[rest],
                                  np.asarray(g_lps)[rest])
    assert list(np.asarray(g_toks)) == \
        list(np.argmax(np.asarray(logits), -1))
