"""Production mesh construction.

NOTE: functions only — importing this module never touches jax device
state.  The dry-run entrypoint sets XLA_FLAGS for 512 host devices *before*
any jax import; everything else sees the real (single-CPU) device set.

Every mesh is built with Auto axes.  Since jax 0.9 `jax.make_mesh`
defaults to Explicit axes, which put shardings into the array types: a
`.at[].set` on a mesh-sharded leaf then demands a `jax.set_mesh` context,
and a jitted step retraces when its inputs' shardings change.  All model
code runs inside `shard_map` and places arrays itself, so it needs
neither.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """TPU v5e production mesh: 16x16 = 256 chips per pod; 2 pods = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(dp: int = 1, tp: int = 1) -> jax.sharding.Mesh:
    """Small mesh over the actually-present devices (smoke tests, examples)."""
    return _mesh((dp, tp), ("data", "model"))
