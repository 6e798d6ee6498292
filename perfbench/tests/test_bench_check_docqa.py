"""The document-QA run on the CPU at a tiny size, with the harness's look
for a chip skipped: requests whose documents the prefix cache serves pass
the reference comparison, the lower-precision control does not, and a
token altered where the engine produces it makes ``correct`` false."""

import os
import time

import jax
import pytest

from perfbench import driver, roofline, spec

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "tiny")
SEED = 2 ** 31 + 4242


@pytest.fixture
def bench(monkeypatch):
    monkeypatch.setitem(roofline.PEAKS, jax.devices()[0].device_kind,
                        roofline.PEAKS["TPU v5 lite"])
    return spec.Bench(root=FIXTURE, base=FIXTURE)


def run(bench, **kw):
    return driver.run_cell(bench, "tiny-docqa", SEED, 1.0, False,
                           time.monotonic(), jax.devices(), **kw)


def test_docqa_sound_run_is_correct_and_the_control_is_not(bench):
    out = run(bench, control=True)
    gap = out["check"]["widest_logit_gap"]
    assert out["correct"] and gap["value"] <= gap["limit"]
    assert out["control_gap"] > gap["limit"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_docqa_token_altered_where_it_is_produced_fails_the_check(
        bench, monkeypatch):
    from repro.serving.engine import PagedEngine
    emit = PagedEngine._emit
    vocab = bench.config("tiny")["model"]["vocab_size"]

    def altered(self, req, slot, tok, lp, now):
        if len(req.output) == 1:
            tok = (tok + 1) % vocab
        return emit(self, req, slot, tok, lp, now)

    monkeypatch.setattr(PagedEngine, "_emit", altered)
    out = run(bench)
    gap = out["check"]["widest_logit_gap"]
    assert not out["correct"] and gap["value"] > gap["limit"]
