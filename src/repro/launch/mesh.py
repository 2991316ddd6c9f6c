"""Production mesh builders.

FUNCTIONS, not module constants — importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).

Mesh topology (TPU v5e pods):
  single-pod  (16, 16)        axes (data, model)   = 256 chips
  multi-pod   (2, 16, 16)     axes (pod, data, model) = 512 chips
The 'pod' axis composes with 'data' for gradient reduction (hierarchical:
reduce-scatter over ICI within a pod, all-reduce across pods over DCN).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this process actually has — smoke/bench mesh."""
    n = jax.device_count()
    return make_auto_mesh((n,), ("data",))


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: sharding follows the
    ``with_sharding_constraint`` hints, as the training step expects
    (``make_mesh`` itself defaults to ``Explicit`` axes)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
