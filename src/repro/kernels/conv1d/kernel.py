"""Pallas TPU kernel: depthwise causal conv1d (framework home of the paper's
technique — the Mamba2/Zamba2 short convolution is a per-channel 1-D stencil).

Per the stencil engine's taxonomy this is the *batched multi-channel* regime:
the channel axis D provides the wide free dimension, so unlike the single-grid
2-D case both the VPU form (shift-FMA, implemented here) and the GEMM form are
viable on TPU; with K = 4 taps the arithmetic intensity is ~K FLOPs/byte and
the kernel is HBM-bound, so the VPU form is roofline-optimal and the 2:4
machinery would only add MXU occupancy — recorded in DESIGN.md §2.

Grid: (B, ceil(T / bt)). Each step DMAs a (bt + K - 1, D) time-halo block
from HBM into VMEM scratch (causal left halo), then accumulates K shifted
VPU FMAs against the (K, D) tap weights held whole in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scopes import KERNEL_PREP
from repro.kernels import common


def _conv_kernel(x_hbm, w_ref, y_ref, scratch, sem, *, k, bt):
    b = pl.program_id(0)
    i = pl.program_id(1)
    cp = pltpu.make_async_copy(
        x_hbm.at[b, pl.ds(i * bt, bt + k - 1), :], scratch, sem)
    cp.start()
    cp.wait()
    acc = jnp.zeros(y_ref.shape[1:], dtype=jnp.float32)
    for j in range(k):                     # static unroll over taps
        acc = acc + w_ref[j][None, :].astype(jnp.float32) * \
            scratch[j:j + bt, :].astype(jnp.float32)
    y_ref[0] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def _conv1d_jit(x, w, *, block_t: int, interpret: bool):
    bsz, t, d = x.shape
    k = w.shape[0]
    bt = min(block_t, t)
    nt = -(-t // bt)
    # causal left halo + pad tail so every tile's DMA window is in bounds
    with jax.named_scope(KERNEL_PREP):
        x = jnp.pad(x, ((0, 0), (k - 1, nt * bt - t), (0, 0)))
        w = w.astype(x.dtype)
    y = pl.pallas_call(
        functools.partial(_conv_kernel, k=k, bt=bt),
        name="conv1d",
        grid=(bsz, nt),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((k, d), lambda b, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, nt * bt, d), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bt + k - 1, d), x.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(x, w)
    with jax.named_scope(KERNEL_PREP):
        return y[:, :t, :]


def conv1d_causal_call(x, w, *, block_t: int = 256,
                       interpret: bool | None = None):
    """x (B, T, D); w (K, D) -> (B, T, D). D must be lane-padded by caller."""
    if interpret is None:
        interpret = common.default_interpret()
    return _conv1d_jit(x, w, block_t=block_t, interpret=interpret)
