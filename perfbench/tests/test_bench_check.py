"""The whole run of a cell on the CPU at a tiny size, with the harness's
look for a chip skipped: the served tokens pass the reference comparison,
the lower-precision control does not, and a token altered where the
engine produces it makes ``correct`` false."""

import os
import time

import jax
import pytest

from perfbench import driver, roofline, spec, weights

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "tiny")
SEED = 2 ** 31 + 777


@pytest.fixture
def bench(monkeypatch):
    monkeypatch.setitem(roofline.PEAKS, jax.devices()[0].device_kind,
                        roofline.PEAKS["TPU v5 lite"])
    return spec.Bench(root=FIXTURE, base=FIXTURE)


def run(bench, cell, **kw):
    return driver.run_cell(bench, cell, SEED, 1.0, False, time.monotonic(),
                           jax.devices(), **kw)


def test_sound_run_is_correct_and_the_control_is_not(bench):
    out = run(bench, "tiny-chat", control=True)
    gap = out["check"]["widest_logit_gap"]
    assert out["correct"] and gap["value"] <= gap["limit"]
    assert out["control_gap"] > gap["limit"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) == {"ttft_p50_s", "itl_p99_ms",
                                   "tokens_per_s", "setup_s"}


def test_a_token_altered_where_it_is_produced_fails_the_check(
        bench, monkeypatch):
    from repro.serving.engine import PagedEngine
    emit = PagedEngine._emit
    vocab = bench.config("tiny")["model"]["vocab_size"]

    def altered(self, req, slot, tok, lp, now):
        if len(req.output) == 2:
            tok = (tok + 1) % vocab
        return emit(self, req, slot, tok, lp, now)

    monkeypatch.setattr(PagedEngine, "_emit", altered)
    out = run(bench, "tiny-batch")
    gap = out["check"]["widest_logit_gap"]
    assert not out["correct"] and gap["value"] > gap["limit"]


def test_weights_are_the_same_in_the_program_tree_and_the_reference():
    from repro.models import Model
    conf = spec.Bench(root=FIXTURE, base=FIXTURE).config("tiny")
    cfg = driver.arch_config("tiny", conf)
    model = Model(cfg, remat=False)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make_params(shapes, [s.n for s in model.plan], SEED)
    key = weights.base_key(SEED)
    wq = params["segments"][0]["attn"]["wq"]["w"]
    for layer in range(cfg.num_layers):
        again = weights.matrix(key, "segments/0/attn/wq/w", layer,
                               *wq.shape[1:])
        assert (again == wq[layer]).all()
    table = params["embed"]["table"]
    assert table.shape[0] >= cfg.vocab_size
    again = weights.matrix(key, "embed/table", 0, cfg.vocab_size,
                           cfg.d_model)
    assert (again == table[:cfg.vocab_size]).all()
