"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.paged_attention import (NEG_INF, live_pages,
                                          pages_per_block)


def _tol(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,hkv,dh,page_size,pages_per_seq",
    [
        (1, 4, 4, 32, 8, 4),     # MHA
        (3, 8, 2, 64, 16, 8),    # GQA
        (2, 8, 1, 64, 16, 4),    # MQA (granite-style)
        (2, 4, 4, 80, 8, 6),     # danube head_dim=80 (non-128 aligned)
    ])
def test_paged_attention_sweep(b, h, hkv, dh, page_size, pages_per_seq,
                               dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    npages = pages_per_seq * b + 2
    q = jax.random.normal(ks[0], (b, h, dh), dtype)
    kp = jax.random.normal(ks[1], (npages, page_size, hkv, dh), dtype)
    vp = jax.random.normal(ks[2], (npages, page_size, hkv, dh), dtype)
    bt = jax.random.randint(ks[3], (b, pages_per_seq), 0, npages)
    smax = pages_per_seq * page_size
    lens = jnp.asarray(
        np.random.default_rng(1).integers(1, smax + 1, b), jnp.int32)
    out = ops.paged_attention(q, kp, vp, bt, lens, page_size=page_size)
    want = ref.paged_attention_ref(q, kp, vp, bt, lens, page_size=page_size)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [None, 8, 24])
def test_paged_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    b, h, hkv, dh, ps, nper = 2, 4, 2, 32, 8, 6
    q = jax.random.normal(ks[0], (b, h, dh))
    kp = jax.random.normal(ks[1], (16, ps, hkv, dh))
    vp = jax.random.normal(ks[2], (16, ps, hkv, dh))
    bt = jax.random.randint(ks[3], (b, nper), 0, 16)
    lens = jnp.array([5, 44], jnp.int32)
    out = ops.paged_attention(q, kp, vp, bt, lens, page_size=ps,
                              window=window)
    want = ref.paged_attention_ref(q, kp, vp, bt, lens, page_size=ps,
                                   window=window)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def _paged_case(seed, b, hkv, g, dh, ps, pages_per_seq, dtype=jnp.float32):
    """Random q and pools, and block tables of distinct physical pages."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    npages = b * pages_per_seq + 3
    q = jax.random.normal(ks[0], (b, hkv * g, dh), dtype)
    kp = jax.random.normal(ks[1], (npages, ps, hkv, dh), dtype)
    vp = jax.random.normal(ks[2], (npages, ps, hkv, dh), dtype)
    perm = np.random.default_rng(seed).permutation(npages)
    bt = jnp.asarray(perm[:b * pages_per_seq].reshape(b, pages_per_seq),
                     jnp.int32)
    return q, kp, vp, bt


@pytest.mark.parametrize(
    "b,hkv,g,dh,ps,pages_per_seq,window,lens",
    [
        # 8 pages per block: contexts a multiple of neither the page nor
        # the block, one of two blocks; an empty slot
        (3, 2, 4, 80, 8, 12, None, [0, 37, 93]),
        (3, 2, 12, 128, 8, 12, None, [1, 0, 96]),
        # a window that cuts mid-page, over one block or two
        (3, 2, 4, 80, 4, 12, 45, [5, 47, 30]),
        (3, 2, 12, 128, 16, 8, 40, [0, 127, 17]),
        # 4 pages per block (6-page tables); a window of one token
        (2, 2, 4, 80, 8, 6, 1, [48, 9]),
    ])
def test_paged_attention_live_pages(b, hkv, g, dh, ps, pages_per_seq,
                                    window, lens):
    """The kernel reads each slot's live pages block by block and matches
    the reference on every slot with context; empty slots give zeros and
    empty partials."""
    q, kp, vp, bt = _paged_case(sum(lens), b, hkv, g, dh, ps, pages_per_seq)
    lens = jnp.asarray(lens, jnp.int32)
    out, m, l = ops.paged_attention(q, kp, vp, bt, lens, page_size=ps,
                                    window=window, return_partials=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, lens, page_size=ps,
                                   window=window)
    act = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(out)[act], np.asarray(want)[act],
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(out)[~act].any()
    assert np.all(np.asarray(l)[~act] == 0)
    assert np.all(np.asarray(m)[~act] == NEG_INF)


def test_pages_per_block_from_shapes():
    """256 KiB of one pool per block: 8 pages at both benchmark widths,
    never more than half the table."""
    assert pages_per_block(16, 8, 80, 2, 1024) == 8     # h2o-danube-1.8b
    assert pages_per_block(16, 8, 128, 2, 512) == 8     # Mistral-Large
    assert pages_per_block(8, 2, 80, 4, 12) == 8
    assert pages_per_block(8, 2, 80, 4, 6) == 4
    assert pages_per_block(16, 8, 128, 4, 512) == 4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_never_reads_dead_pages(dtype):
    """Every pool page outside each slot's live range (past its context,
    before its window, in no table, the whole table of an empty slot) is
    NaN: an output that stays finite and equal to the reference proves
    the kernel never reads those pages."""
    b, hkv, g, dh, ps, nper, window = 4, 2, 4, 80, 8, 12, 27
    q, kp, vp, bt = _paged_case(7, b, hkv, g, dh, ps, nper, dtype)
    lens = jnp.asarray([45, 0, 96, 3], jnp.int32)
    first, count = live_pages(np.asarray(lens), ps, window)
    live = np.zeros(kp.shape[0], bool)
    for i in range(b):
        live[np.asarray(bt)[i, first[i]:first[i] + count[i]]] = True
    assert live.sum() == count.sum() < kp.shape[0] // 2
    dead = jnp.asarray(~live)[:, None, None, None]
    out = ops.paged_attention(q, jnp.where(dead, jnp.nan, kp),
                              jnp.where(dead, jnp.nan, vp), bt, lens,
                              page_size=ps, window=window)
    want = ref.paged_attention_ref(q, kp, vp, bt, lens, page_size=ps,
                                   window=window)
    out = np.asarray(out, np.float32)
    assert np.all(np.isfinite(out))
    act = np.asarray(lens) > 0
    np.testing.assert_allclose(out[act], np.asarray(want, np.float32)[act],
                               **_tol(dtype))


def test_paged_attention_partials_merge():
    """(m, l) partials from two half-caches must merge to the full result —
    the DistAttention contract."""
    from repro.core.distkv.dist_attention import merge_partials_tree
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    b, h, hkv, dh, ps = 2, 4, 2, 32, 8
    kp = jax.random.normal(ks[1], (8, ps, hkv, dh))
    vp = jax.random.normal(ks[2], (8, ps, hkv, dh))
    q = jax.random.normal(ks[0], (b, h, dh))
    bt = jnp.array([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    lens = jnp.array([30, 32], jnp.int32)
    full = ops.paged_attention(q, kp, vp, bt, lens, page_size=ps)

    # split each sequence's pages into two shards of 2 pages
    o1, m1, l1 = ops.paged_attention(q, kp, vp, bt[:, :2],
                                     jnp.minimum(lens, 16), page_size=ps,
                                     return_partials=True)
    lens2 = jnp.maximum(lens - 16, 0)
    # second shard sees positions 16.. => emulate with its own table; mask
    # by (lens-16) and offset handled because pages are logical-in-order
    o2, m2, l2 = ops.paged_attention(q, kp, vp, bt[:, 2:], lens2,
                                     page_size=ps, return_partials=True)
    merged = merge_partials_tree([o1 * l1[..., None], o2 * l2[..., None]],
                                 [m1, m2], [l1, l2])
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,hkv,dh,qb,kb,causal,window",
    [
        (1, 128, 4, 4, 64, 64, 64, True, None),
        (2, 256, 8, 2, 64, 128, 128, True, None),
        (2, 256, 8, 2, 64, 128, 64, True, 64),
        (1, 128, 4, 1, 32, 32, 32, False, None),
        (2, 192, 6, 2, 80, 64, 64, True, None),  # non-pow2 heads/dh
    ])
def test_flash_prefill_sweep(b, s, h, hkv, dh, qb, kb, causal, window,
                             dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, dh), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, dh), dtype)
    out = ops.flash_prefill(q, k, v, causal=causal, window=window,
                            q_block=qb, kv_block=kb)
    want = ref.flash_prefill_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_ssd_chunked_vs_sequential():
    """Chunked SSD (production path) vs the sequential recurrence oracle."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    b, l, h, p, g, n = 2, 64, 4, 16, 2, 8
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = jax.random.normal(ks[3], (b, l, g, n))
    C = jax.random.normal(ks[4], (b, l, g, n))
    for chunk in (8, 16, 32, 64):
        y, st = ssd_chunked(x, dt, A, B, C, chunk)
        y_ref, st_ref = ref.ssd_scan_ref(x, dt, A, B, C)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("chunk", [16, 32, 64])
@pytest.mark.parametrize("g", [1, 2])
def test_ssd_scan_pallas_vs_sequential(chunk, g):
    """Pallas SSD kernel (VMEM state carry) vs the sequential oracle."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    b, l, h, p, n = 2, 128, 4, 16, 8
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = jax.random.normal(ks[3], (b, l, g, n))
    C = jax.random.normal(ks[4], (b, l, g, n))
    y, st = ops.ssd_scan(x, dt, A, B, C, chunk=chunk)
    yr, sr = ref.ssd_scan_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), rtol=2e-4,
                               atol=2e-4)
