"""The serving decode kernel compiles for a TPU v5e at real width.

No chip is needed: the TPU compiler compiles for a described v5e topology.
Interpret-mode tests (``test_kernels.py``) cannot see what Mosaic refuses —
unaligned tiles, too much VMEM, an unpartitionable grid — so these compile
``paged_attention`` with ``interpret=False`` at h2o-danube-1.8b's decode
shapes: 8 slots, 32 query heads over 8 KV heads, head_dim 80, 16-token
pages, 1024 pages per sequence (its 16k context), 2049 pool pages.

The topology is described inside a fixture, never at import: the process
that describes it loads the TPU library and keeps it until it exits.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import paged_attention

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
