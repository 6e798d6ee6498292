"""The traffic generator and the order statistics the benchmark reports."""

import json
import os

import numpy as np
import pytest

from perfbench import generator
from perfbench.stats import percentile, quartile_spread

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = os.path.join(os.path.dirname(HERE), "traffic")
BIG_SEED = 2 ** 31 + 12345


def mix(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


def flat(specs):
    return [(s.prompt, s.max_new, s.due, s.doc) for s in specs]


@pytest.mark.parametrize("name", ["chat", "docqa"])
def test_open_loop_same_seed_same_requests(name):
    m = mix(name)
    a = generator.Traffic(m, BIG_SEED, 32000).open_loop(12.0)
    b = generator.Traffic(m, BIG_SEED, 32000).open_loop(12.0)
    assert flat(a) == flat(b)


@pytest.mark.parametrize("name", ["chat", "docqa"])
def test_open_loop_seeds_differ_in_tokens_not_in_sizes_or_times(name):
    m = mix(name)
    a = generator.Traffic(m, 1, 32000).open_loop(12.0)
    b = generator.Traffic(m, 2, 32000).open_loop(12.0)
    assert [s.prompt for s in a] != [s.prompt for s in b]
    assert [(len(s.prompt), s.max_new, s.due, s.doc) for s in a] == \
        [(len(s.prompt), s.max_new, s.due, s.doc) for s in b]


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    m = mix("chat")
    rate, lead, w = m["arrivals"]["rate_per_s"], m["lead_in_s"], 40.0
    specs = generator.Traffic(m, 3, 32000).open_loop(w)
    win = [s for s in specs if s.due >= lead]
    assert len(win) == round(rate * w)
    assert all(lead <= s.due < lead + w for s in win)
    assert all(0 <= s.due < lead for s in specs if s not in win)


def test_lengths_stay_in_bounds_and_follow_the_distribution():
    m = mix("chat")
    specs = generator.Traffic(m, 4, 32000).batch(400)
    plen = np.array([len(s.prompt) for s in specs])
    assert plen.min() >= 64 and plen.max() <= 8192
    assert abs(np.median(plen) - 1024) <= 32
    outs = np.array([s.max_new for s in specs])
    assert outs.min() >= 16 and outs.max() <= 512
    assert abs(np.median(outs) - 128) <= 4


def test_unique_prompts_share_no_first_token_and_skip_the_warmup_token():
    specs = generator.Traffic(mix("chat"), 5, 32000).batch(500)
    firsts = [s.prompt[0] for s in specs]
    assert len(set(firsts)) == len(firsts)
    assert generator.WARMUP_TOKEN not in firsts


def test_documents_repeat_by_zipf_rank_with_fixed_lengths():
    m = mix("docqa")
    t1 = generator.Traffic(m, 6, 32000)
    t2 = generator.Traffic(m, 7, 32000)
    assert [len(d) for d in t1.docs] == [len(d) for d in t2.docs]
    assert all(2048 <= len(d) <= 8192 for d in t1.docs)
    specs = t1.batch(600)
    counts = np.bincount([s.doc for s in specs], minlength=32)
    assert counts[0] == counts.max() and counts[0] > 3 * counts[-1]
    for s in specs:
        doc = t1.docs[s.doc]
        assert s.prompt[:len(doc)] == doc
        assert 32 <= len(s.prompt) - len(doc) <= 128


def test_closed_loop_blocks_repeat_the_same_lengths():
    m = mix("batch")
    stream = generator.Traffic(m, 8, 32000).stream()
    other = generator.Traffic(m, 9, 32000).stream()
    b = m["block"]
    blocks = [[next(stream) for _ in range(b)] for _ in range(3)]
    lens = [sorted(len(s.prompt) for s in blk) for blk in blocks]
    assert lens[0] == lens[1] == lens[2]
    assert [len(s.prompt) for s in blocks[0]] != \
        [len(s.prompt) for s in blocks[1]]
    again = [next(other) for _ in range(b)]
    assert [len(s.prompt) for s in again] == \
        [len(s.prompt) for s in blocks[0]]
    assert [s.prompt for s in again] != [s.prompt for s in blocks[0]]


def test_max_context_covers_every_request():
    for name in ("chat", "batch", "docqa"):
        m = mix(name)
        t = generator.Traffic(m, 9, 32000)
        specs = t.batch(64)
        assert max(len(s.prompt) + s.max_new for s in specs) <= \
            generator.max_context(m)


@pytest.mark.parametrize("values,q,want", [
    ([], 50, float("inf")),
    ([3.0], 99, 3.0),
    ([1, 2, 3, 4], 50, 3),
    ([5, 1, 4, 2, 3], 90, 5),
    (list(range(100)), 99, 99),
    (list(range(100)), 0, 0),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert percentile(values, q) == want


def test_quartile_spread_uses_the_default_quantiles():
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    # statistics.quantiles(n=4), exclusive method: 10.75, 12.5, 14.25
    assert quartile_spread(vals) == pytest.approx((14.25 - 10.75) / 12.5)
