"""Random weights from the seed, made by the benchmark and not by the
program, so that the reference can make the same ones again without
taking anything from the program.

Every matrix entry is an int8 drawn from its own counter-based stream
times a power of two, so it is exact in bfloat16 and bit-identical
wherever and however it is computed: row ``r`` of leaf ``name`` in layer
``l`` is ``bits(fold_in(fold_in(fold_in(key(seed), crc32(name)), l), r))``.
A leaf's scale is the power of two nearest to ``1/sqrt(fan_in)`` (the
embedding table's to 0.02), the program's own initial scales; norm scales
are ones and biases zeros. A leaf with more than two dimensions per layer
is a stack of matrices (an MoE layer's experts): matrix ``i`` of its
leading axes, counted flat, has ``i`` folded in after the layer, and its
scale follows its fan-in ``shape[-2]``.
"""

from __future__ import annotations

import math
import zlib
from typing import Tuple

import jax
import jax.numpy as jnp

INT8_STD = math.sqrt((256 ** 2 - 1) / 12.0)   # std of uniform int8
EMBED_STD = 0.02


def scale_exp(name: str, shape: Tuple[int, ...]) -> int:
    """e such that the leaf's entries are int8 * 2**-e."""
    std = EMBED_STD if name.endswith("table") else 1.0 / math.sqrt(shape[-2])
    return int(round(math.log2(INT8_STD / std)))


def base_key(seed: int):
    return jax.random.PRNGKey(seed)


def leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def matrix(key, name: str, layer, rows: int, cols: int, dtype=jnp.bfloat16,
           index=None):
    """Rows ``[0, rows)`` of leaf ``name`` in ``layer`` (traced or int);
    with ``index``, of matrix ``index`` of the leaf's stack in that layer
    (expert ``i`` of an MoE layer, alone)."""
    k = jax.random.fold_in(leaf_key(key, name), layer)
    if index is not None:
        k = jax.random.fold_in(k, index)
    e = scale_exp(name, (rows, cols))
    bits = jax.vmap(lambda r: jax.random.bits(
        jax.random.fold_in(k, r), (cols,), jnp.uint8))(jnp.arange(rows))
    vals = jax.lax.bitcast_convert_type(bits, jnp.int8)
    return (vals.astype(jnp.float32) * (2.0 ** -e)).astype(dtype)


def leaf(key, name: str, shape: Tuple[int, ...], dtype, layers: int,
         first_layer: int):
    """One leaf of the program's tree. ``layers`` > 0 means the leaf is a
    stack of that many layers along its first axis."""
    per = shape[1:] if layers else shape
    if name.endswith("scale"):
        return jnp.ones(shape, dtype)
    if name.endswith("/b"):
        return jnp.zeros(shape, dtype)
    if len(per) < 2:
        raise ValueError(f"no weight rule for {name} of shape {shape}")

    def one(layer):
        if len(per) == 2:
            return matrix(key, name, layer, *per, dtype=dtype)
        return stack(key, name, layer, per, dtype)

    if not layers:
        return one(first_layer)
    return jax.vmap(one)(first_layer + jnp.arange(layers))


def stack(key, name: str, layer, shape: Tuple[int, ...], dtype):
    """A stack of matrices of ``shape`` (leading axes, rows, cols) in
    ``layer``: matrix ``i`` of the leading axes, counted flat, is
    ``matrix(..., index=i)``."""
    *lead, rows, cols = shape
    mats = jax.vmap(lambda i: matrix(key, name, layer, rows, cols, dtype,
                                     index=i))(jnp.arange(math.prod(lead)))
    return mats.reshape(shape)


def _name(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def make_params(shapes, seg_layers, seed: int):
    """The program's parameter tree, filled from ``seed`` on the default
    device in one jitted call. ``shapes`` is the tree of
    ``jax.ShapeDtypeStruct`` the program's init would return;
    ``seg_layers`` the number of layers of each segment, in order."""
    offsets, off = [], 0
    for n in seg_layers:
        offsets.append(off)
        off += n

    def build(key):
        def one(path, s):
            name = _name(path)
            parts = name.split("/")
            if parts[0] == "segments":
                i = int(parts[1])
                n = seg_layers[i]
                return leaf(key, name, s.shape, s.dtype, n if n > 1 else 0,
                            offsets[i])
            return leaf(key, name, s.shape, s.dtype, 0, 0)
        return jax.tree_util.tree_map_with_path(one, shapes)

    return jax.jit(build)(base_key(seed))

