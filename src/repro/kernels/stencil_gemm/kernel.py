"""Pallas TPU kernel: dense kernel-matrix GEMM over input windows.

The paper-faithful §3.2.1 executor (generalized TCStencil): the banded
(L, 2L) kernel matrix multiplies 2L-row input windows, updating L outputs
per window column. This is the *dense* Tensor-Core analogue — it performs
the full 2x-redundant MAC count that SpTC (and our compressed kernel)
eliminates; it exists as the measured baseline for that comparison.

Blocking: kernel matrix whole in VMEM (tiny); windows tiled (1, 2L, bn);
MXU does the (L, 2L) x (2L, bn) dot per grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common
from repro.kernels.common import round_up


def _gemm_kernel(km_ref, win_ref, y_ref):
    km = km_ref[:]                    # (L, K)
    win = win_ref[0]                  # (K, bn)
    y_ref[0] = jnp.dot(km, win, precision=common.dot_precision(win.dtype),
                       preferred_element_type=jnp.float32
                       ).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _windows_gemm_jit(km, windows, *, block_n: int, interpret: bool):
    l, k = km.shape
    t, k2, c = windows.shape
    if k2 != k:
        raise ValueError(f"K mismatch {k2} vs {k}")
    bn = min(block_n, round_up(c, 128))
    c_pad = round_up(c, bn)
    if c_pad != c:
        windows = jnp.pad(windows, ((0, 0), (0, 0), (0, c_pad - c)))
    y = pl.pallas_call(
        _gemm_kernel,
        grid=(t, c_pad // bn),
        in_specs=[
            pl.BlockSpec((l, k), lambda i, j: (0, 0)),
            pl.BlockSpec((1, k, bn), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, l, bn), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((t, l, c_pad), windows.dtype),
        interpret=interpret,
    )(km.astype(windows.dtype), windows)
    return y[:, :, :c]


def windows_gemm_call(km, windows, *, block_n: int = 512,
                      interpret: bool | None = None):
    """km (L, K); windows (T, K, C) -> (T, L, C)."""
    if interpret is None:
        interpret = common.default_interpret()
    return _windows_gemm_jit(km, windows, block_n=block_n,
                             interpret=interpret)
