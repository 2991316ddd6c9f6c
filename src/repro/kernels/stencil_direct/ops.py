"""Jitted wrappers: direct Pallas stencil for 1-D and 2-D problems."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import common
from repro.kernels.stencil_direct.kernel import stencil2d_call


def _taps(weights: np.ndarray):
    """Static (u, v, weight) tuple of non-zero taps (star taps pruned)."""
    kh, kw = weights.shape
    return tuple((u, v, float(weights[u, v]))
                 for u in range(kh) for v in range(kw)
                 if weights[u, v] != 0)


def stencil2d(weights: np.ndarray, x, *, th: int = 128,
              interpret: bool | None = None):
    """weights (2rh+1, 2rw+1); x (H+2rh, W+2rw) -> (H, W)."""
    if interpret is None:
        interpret = common.default_interpret()
    weights = np.asarray(weights)
    kh, kw = weights.shape
    rh, rw = (kh - 1) // 2, (kw - 1) // 2
    h_in, w_in = x.shape
    th = min(th, common.round_up(h_in - 2 * rh, common.SUBLANES))
    tw = min(512, common.round_up(w_in - 2 * rw, common.LANES))
    return stencil2d_call(jnp.asarray(x), taps=_taps(weights), rh=rh, rw=rw,
                          th=th, tw=tw, interpret=interpret)


def stencil1d(weights: np.ndarray, x, *, interpret: bool | None = None):
    """1-D stencil as a 2-D problem with rh = 0.

    x: (N + 2r,) -> (N,). The row dim is tiled to expose parallelism: the
    flat vector is viewed as (rows, W) with per-row halo columns overlapping.
    """
    weights = np.asarray(weights).reshape(1, -1)
    y = stencil2d(weights, jnp.asarray(x)[None, :], interpret=interpret)
    return y[0]
