"""Engine backend -> Pallas kernel builders + backend applicability.

``applicable_backends`` is the tuner's candidate universe: which of
``engine.BACKENDS`` can execute a given spec on a given device kind.
The jnp backends (direct/gemm/sptc) run anywhere XLA does; the Pallas
backends only enter the candidate set on a real TPU (off-TPU they fall
back to interpret mode — bit-faithful but Python-speed, never a winning
plan) unless ``REPRO_TUNER_INCLUDE_PALLAS=1`` forces them in for
correctness sweeps.
"""
from __future__ import annotations

import os
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stencil import StencilSpec

JNP_BACKENDS = ("direct", "gemm", "sptc")
PALLAS_BACKENDS = ("pallas_direct", "pallas_mxu", "pallas_sptc")


def backend_universe(device: str | None = None) -> str:
    """Provenance tag for the candidate universe tuning ran against.

    Recorded in the tuner plan key so plans tuned with the Pallas
    backends forced in (``REPRO_TUNER_INCLUDE_PALLAS=1`` correctness
    sweeps — interpret mode, Python speed) can never be served as
    winning plans to a plain-CPU process, and vice versa.
    """
    device = device if device is not None else jax.default_backend()
    if device == "tpu" or os.environ.get("REPRO_TUNER_INCLUDE_PALLAS") == "1":
        return "jnp+pallas"
    return "jnp"


def applicable_backends(spec: StencilSpec,
                        device: str | None = None) -> Tuple[str, ...]:
    """Backends able to execute ``spec`` on ``device`` (default: current).

    1-D specs get no Pallas backend: a 1-D grid reaches the kernels as an
    (N, 1) column whose lane axis pads from 1 to 128, a 128x inflation
    that runs out of HBM at paper size.  An engine built explicitly with
    ``backend="pallas_*"`` still runs a 1-D spec.
    """
    out = list(JNP_BACKENDS)
    if backend_universe(device) == "jnp+pallas" and spec.ndim > 1:
        out.extend(PALLAS_BACKENDS)
    return tuple(out)


def build(spec: StencilSpec, backend: str, L: int) -> Callable:
    """Whole-stencil applicator for the 'pallas_direct' backend."""
    if backend != "pallas_direct":
        raise ValueError(f"dispatch.build handles pallas_direct, got {backend}")
    from repro.kernels.stencil_direct.ops import stencil1d, stencil2d

    w = np.asarray(spec.weights)
    r = spec.radius

    if spec.ndim == 1:
        return lambda x: stencil1d(w, x)

    if spec.ndim == 2:
        return lambda x: stencil2d(w, x)

    # 3-D: decompose the leading axis (paper §3.2.1 row decomposition,
    # lifted one dimension): y[a] = sum_u  stencil2d(w[u]) applied to x[a+u].
    def fn3d(x):
        n1 = x.shape[0] - 2 * r
        acc = None
        for u in range(2 * r + 1):
            if not np.any(w[u] != 0):
                continue
            part = jax.vmap(lambda s, wu=w[u]: stencil2d(wu, s))(x[u:u + n1])
            acc = part if acc is None else acc + part
        if acc is None:       # all-zero kernel: every slab skipped
            out_shape = (n1,) + tuple(s - 2 * r for s in x.shape[1:])
            return jnp.zeros(out_shape, dtype=x.dtype)
        return acc
    return fn3d
