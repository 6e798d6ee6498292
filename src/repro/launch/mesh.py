"""Production mesh builders (TPU v5e pods; 256 chips/pod).

Defined as FUNCTIONS so importing this module never touches jax device
state — ``dryrun.py`` must set XLA_FLAGS before the first jax call.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules place
    arrays with ``NamedSharding``/``with_sharding_constraint`` and leave the
    rest to the partitioner, so no jit needs an enclosing ``jax.set_mesh``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices actually exist (tests/examples)."""
    n = len(jax.devices())
    mp = model_parallel if n % model_parallel == 0 else 1
    return make_mesh((n // mp, mp), ("data", "model"))


def data_axes(mesh) -> tuple:
    """The axes batch is sharded over (pod+data when multi-pod)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
