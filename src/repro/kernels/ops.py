"""Public wrappers for the Pallas kernels.

Every ``pl.pallas_call`` here lowers to Mosaic on a TPU. Only where JAX's
default backend is the CPU (the test suite, a machine without a chip) do the
kernels run in Pallas interpret mode, so the same code paths are testable
there. To compile a kernel for a TPU from a CPU process (a described
topology), call the kernel module's function with ``interpret=False``.
"""

from __future__ import annotations

import jax

from repro.kernels.flash_prefill import flash_prefill as _flash
from repro.kernels.paged_attention import paged_attention as _paged


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def paged_attention(q, k_pages, v_pages, block_tables, context_lens, *,
                    page_size, window=None, return_partials=False):
    return _paged(q, k_pages, v_pages, block_tables, context_lens,
                  page_size=page_size, window=window,
                  return_partials=return_partials, interpret=_interpret())


def flash_prefill(q, k, v, *, causal=True, window=None, q_block=128,
                  kv_block=128):
    return _flash(q, k, v, causal=causal, window=window, q_block=q_block,
                  kv_block=kv_block, interpret=_interpret())


def ssd_scan(x, dt, A, B, C, *, chunk=64):
    from repro.kernels.ssd_scan import ssd_scan as _ssd
    return _ssd(x, dt, A, B, C, chunk=chunk, interpret=_interpret())
