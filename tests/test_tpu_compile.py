"""The serving decode kernel compiles for a TPU v5e at real width.

No chip is needed: the TPU compiler compiles for a described v5e topology.
Interpret-mode tests (``test_kernels.py``) cannot see what Mosaic refuses —
unaligned tiles, too much VMEM, an unpartitionable grid — so these compile
``paged_attention`` with ``interpret=False`` at h2o-danube-1.8b's decode
shapes: 8 slots, 32 query heads over 8 KV heads, head_dim 80, 16-token
pages, 1024 pages per sequence (its 16k context), 2049 pool pages; and at
the benchmark's serving widths: danube with 16 slots and 4097 pool pages,
and Mistral-Large-2407's 96 query heads over 8 KV heads at head_dim 128.
The sampler is compiled at the benchmark's slots x vocabulary shapes too.

The topology is described inside a fixture, never at import: the process
that describes it loads the TPU library and keeps it until it exits.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import paged_attention
from repro.models import sampling

PAGE_SIZE = 16
SLOTS = 8
POOL_PAGES = 2049
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler log files
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler can be loaded here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache but never read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("window,return_partials", [
    (None, False),
    ("model", False),   # the model's 4096-token sliding window
    (None, True),       # DistAttention (o, m, l) partials
])
def test_paged_attention_compiles_for_v5e(one_chip, window,
                                          return_partials):
    cfg = get_config("h2o-danube-1.8b")
    window = cfg.sliding_window if window == "model" else window
    pages_per_seq = cfg.max_seq_len // PAGE_SIZE

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = spec((POOL_PAGES, PAGE_SIZE, cfg.num_kv_heads, cfg.head_dim),
                jnp.bfloat16)
    compiled = paged_attention.lower(
        spec((SLOTS, cfg.num_heads, cfg.head_dim), jnp.bfloat16), pool, pool,
        spec((SLOTS, pages_per_seq), jnp.int32), spec((SLOTS,), jnp.int32),
        page_size=PAGE_SIZE, window=window, return_partials=return_partials,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()  # Mosaic, not XLA
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.parametrize("slots,heads,kv_heads,head_dim,pages_per_seq,"
                         "window,pool_pages", [
    # h2o-danube-1.8b as the benchmark serves it: 16 slots, 16k context,
    # its 4096-token window, 4096 pool pages
    (16, 32, 8, 80, 1024, 4096, 4097),
    # Mistral-Large-2407's attention: 96 query heads over 8 KV heads,
    # head_dim 128, an 8k context, 8192 pool pages
    (16, 96, 8, 128, 512, None, 8193),
])
def test_paged_attention_compiles_at_serving_width(
        one_chip, slots, heads, kv_heads, head_dim, pages_per_seq, window,
        pool_pages):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = spec((pool_pages, PAGE_SIZE, kv_heads, head_dim), jnp.bfloat16)
    compiled = paged_attention.lower(
        spec((slots, heads, head_dim), jnp.bfloat16), pool, pool,
        spec((slots, pages_per_seq), jnp.int32), spec((slots,), jnp.int32),
        page_size=PAGE_SIZE, window=window, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < V5E_HBM_BYTES


def _hlo_ops(text, op):
    """The element counts of each array the compiled module's ``op``
    instructions produce (a tuple-shaped op gives one per member)."""
    out = []
    for lhs in re.findall(rf"= (\(?[^=]*?\)?) {op}\(", text):
        out.append([math.prod(int(d) for d in dims.split(",") if d)
                    for dims in re.findall(r"\[([\d,]*)\]", lhs)])
    return out


@pytest.mark.parametrize("n,v", [
    (128, 102400),  # DeepSeek-V2's stage: 128 slots, its whole vocabulary
    (16, 32768),    # Mistral-Large-2407: 16 slots
])
def test_sampler_compiles_without_full_vocabulary_index_ops(one_chip, n, v):
    """The sampler reads no (n, V) gather and writes no (n, V) scatter: one
    stable sort gives values and ids together, the kept set is a threshold
    mask, and an all-greedy batch takes the conditional's argmax branch."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(sampling.sample_batch).lower(
        spec((n, v), jnp.float32), spec((n,), jnp.int32),
        spec((n,), jnp.int32), spec((n,), jnp.float32),
        spec((n,), jnp.int32), spec((n,), jnp.float32)).compile().as_text()
    for op in ("gather", "scatter"):
        sizes = [max(s) for s in _hlo_ops(text, op)]
        assert all(s < n * v for s in sizes), (op, sizes)
    sorts = [s for s in _hlo_ops(text, "sort") if max(s) >= n * v]
    assert sorts == [[n * v, n * v]]  # (values, ids), once
    assert len(_hlo_ops(text, "conditional")) == 1
