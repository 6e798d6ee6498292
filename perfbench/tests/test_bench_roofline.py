"""Peaks, the operations and bytes of the model's steps, and the
per-layer readers over a trace built by hand."""

import json
import os

import pytest

from perfbench import driver, roofline, spec, tracefile
from perfbench.tracefile import Event, Trace

with open(os.path.join(spec.HERE, "configs", "h2o-danube-1.8b.json")) as f:
    DANUBE = json.load(f)["model"]
with open(os.path.join(spec.HERE, "configs",
                       "mistral-large-123b-3l.json")) as f:
    MISTRAL = json.load(f)["model"]
V5E = roofline.peaks("TPU v5 lite")


def test_peaks_are_the_published_v5e_numbers():
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["hbm_bytes"] == 16 * 2 ** 30


def test_an_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_published_sizes():
    # 1.83 B parameters, 1.75 B with the unembedding tied; 61,440 B of
    # bf16 KV per token
    assert roofline.weight_bytes(DANUBE) == pytest.approx(3.50e9, rel=0.01)
    assert roofline.weight_bytes(dict(DANUBE, tie_word_embeddings=False)) \
        == pytest.approx(3.67e9, rel=0.01)
    assert roofline.kv_bytes_per_token(DANUBE) == 61440
    # 1.384 B parameters per layer of the 123 B model; 12,288 B per token
    assert roofline.layer_matmul_params(MISTRAL) == \
        pytest.approx(1.384e9, rel=0.01)
    assert roofline.kv_bytes_per_token(MISTRAL) == 12288


@pytest.mark.parametrize("start,length", [(0, 1), (0, 2048), (3000, 2048),
                                          (4095, 3), (5000, 700), (0, 8192)])
@pytest.mark.parametrize("model", [DANUBE, MISTRAL], ids=["swa", "global"])
def test_attended_keys_of_a_chunk_match_the_brute_force_sum(model, start,
                                                            length):
    want = sum(roofline.attended(model, i + 1)
               for i in range(start, start + length))
    assert roofline._sum_attended(model, start, length) == want


def test_decode_step_counts_real_context_only():
    ctxs = [100, 5000, 16000]
    kv = roofline.kv_bytes_per_token(DANUBE)
    want_bytes = roofline.weight_bytes(DANUBE) + kv * (100 + 4096 + 4096) + \
        3 * kv
    assert roofline.decode_bytes(DANUBE, ctxs) == want_bytes
    per_key = 24 * 4 * 32 * 80
    want_flops = 3 * 2.0 * roofline.matmul_params(DANUBE) + \
        per_key * (100 + 4096 + 4096)
    assert roofline.decode_flops(DANUBE, ctxs) == want_flops
    # a small decode batch is bound by bytes
    t = roofline.least_time(want_flops, want_bytes, V5E)
    assert t == pytest.approx(want_bytes / 819e9)


def test_prefill_flops_count_matmuls_once_per_token_and_one_logit_row():
    d, v = DANUBE["hidden_size"], DANUBE["vocab_size"]
    body = roofline.matmul_params(DANUBE) - v * d
    got = roofline.prefill_flops(DANUBE, 0, 1)
    assert got == 2.0 * body + 2.0 * v * d + 24 * 4 * 32 * 80


def _trace():
    dev = "/device:TPU:0"
    modules = [Event("jit__prefill_chunk_fn", 1.0, 1.2),
               Event("jit__decode_fn", 1.3, 1.35),
               Event("jit_sample_batch", 1.36, 1.37),
               Event("jit__decode_fn", 1.5, 1.55)]
    ops = [Event("fusion.1", 1.0, 1.2), Event("fusion.2", 1.3, 1.35),
           Event("gather", 1.36, 1.37), Event("fusion.2", 1.5, 1.55)]
    host = [Event("bench.traced", 1.0, 2.0), Event("bench.poll", 1.0, 1.4),
            Event("bench.poll", 1.45, 1.6), Event("bench.wait", 1.6, 2.0),
            Event("np.asarray", 1.37, 1.45)]
    return Trace((1.0, 2.0), {dev: modules}, {dev: ops}, host)


def _run(trace, **kw):
    run = driver.Run(DANUBE, V5E, 10.0, [], [[5000, 100]], {},
                     memory_peak_bytes=3 * 2 ** 30, trace=trace)
    for k, v in kw.items():
        setattr(run, k, v)
    return run


def test_trace_busy_idle_and_attribution():
    tr = _trace()
    assert tr.window_s == pytest.approx(1.0)
    assert tr.busy_s() == pytest.approx(0.2 + 0.05 + 0.01 + 0.05)
    assert tr.work_s() == pytest.approx(0.6)
    idle = sum(b - a for a, b in tr.idle_with_work())
    assert idle == pytest.approx(0.6 - 0.31)
    gaps = dict(tr.idle_gaps())
    assert gaps["np.asarray"] == pytest.approx(0.13)
    assert gaps["bench.poll"] == pytest.approx(0.1 + 0.01 + 0.05)
    assert sum(gaps.values()) == pytest.approx(idle)
    assert tr.top_ops()[0] == ("fusion.1", pytest.approx(0.2))
    assert [e.start for e in tr.program_runs("_decode_fn")] == [1.3, 1.5]


def test_readers_over_a_hand_built_trace():
    bench = spec.Bench()
    ctxs = [[5000, 100], [5001]]
    run = _run(_trace(), traced_decodes=ctxs, traced_chunks=[(0, 2048)])
    assert bench.reader("decode_step_ms")(run) == pytest.approx(50.0)
    assert bench.reader("prefill_ms_per_ktok")(run) == \
        pytest.approx(200.0 / 2.048)
    least = sum(roofline.least_time(roofline.decode_flops(DANUBE, c),
                                    roofline.decode_bytes(DANUBE, c), V5E)
                for c in ctxs)
    assert bench.reader("decode_step_roofline")(run) == \
        pytest.approx(100 * least / 0.1)
    flops = sum(roofline.decode_flops(DANUBE, c) for c in ctxs) + \
        roofline.prefill_flops(DANUBE, 0, 2048)
    assert bench.reader("step_mfu")(run) == \
        pytest.approx(100 * flops / (0.3 * 197e12))
    assert bench.reader("device_idle_share")(run) == \
        pytest.approx(100 * 0.29 / 0.6)
    assert bench.reader("peak_hbm_gib")(run) == 3.0
    assert bench.reader("decode_batch_mean")(run) == 2.0


def test_readers_return_nothing_when_runs_and_steps_do_not_pair():
    bench = spec.Bench()
    run = _run(_trace(), traced_decodes=[[5]], traced_chunks=[(0, 8)])
    assert bench.reader("decode_step_roofline")(run) is None
    assert bench.reader("step_mfu")(run) is None
    assert bench.reader("prefix_hit_share")(run) is None
    assert bench.reader("queue_wait_p50_s")(run) is None
    none = _run(None)
    for name in ("decode_step_ms", "device_idle_share", "prefill_ms_per_ktok"):
        assert bench.reader(name)(none) is None


def test_interval_arithmetic():
    assert tracefile._union([(3, 4), (1, 2), (1.5, 2.5)]) == \
        [(1, 2.5), (3, 4)]
    assert tracefile._subtract([(0, 10)], [(1, 2), (5, 12)]) == \
        [(0, 1), (2, 5)]
