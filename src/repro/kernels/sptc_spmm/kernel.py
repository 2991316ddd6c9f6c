"""Pallas TPU kernels: 2:4-compressed SpMM (simulated Sparse Tensor Core).

Two entry points:

``sptc_spmm_call`` — the v1 building block: a plain compressed SpMM over a
pre-swapped RHS.  Faithful executable semantics of ``mma.sp``: per output
row i and 4-wide reduction segment s, only the two RHS rows selected by
the 2-bit metadata contribute.  TPU has no SpTC, so the kernel realizes
the selection as an in-VMEM decompression (VPU one-hot expansion over the
tiny K dim) followed by a dense MXU matmul over the N (free) dimension.

``sptc_fused_call`` — the v2 fused stencil executor (paper §3.3 "zero
runtime overhead"): ONE Pallas program that, per (N-tile, row-tile) grid
step,

  1. DMAs the overlapping input window of its ``B`` output rows straight
     from HBM into VMEM scratch, double-buffered across sequential grid
     steps (the t+1 window prefetches while tile t computes);
  2. folds the strided row swap AND the 2-bit metadata gather into the
     decompression's comparison positions — the swap permutation is the
     closed form ``p odd: p <-> p±L`` so it is derived from an iota
     inside the kernel, and the metadata is unpacked in-register from
     the packed ``meta_bits`` words.  Nothing is permuted or gathered
     outside the kernel;
  3. runs the dense MXU matmul (f32, or bf16 inputs with f32
     accumulation via ``compute_dtype="bfloat16"``).

Star fast path (``star_fast=True``): when the composed swap∘meta gather
is the identity band of the taps (see ``core.sparsify
.contiguous_band_values``), the metadata carries no information — the
kernel skips the one-hot decompression and performs K/2 shifted VPU FMAs
over the banded value layout, touching no metadata at all.

Blocking: the compressed operand (M = L, K/2) and metadata words are tiny
and live whole in VMEM; the input stays in HBM (``pl.ANY``) because the
overlapping windows cannot be expressed as disjoint BlockSpec tiles.  To
fit v5e's (8, 128) f32 tile, one grid step stacks ``B // L`` row tiles of
``L`` (``B`` = the least multiple of ``L`` filling whole sublane tiles: 8
rows for L = 4, 24 for L = 6) and DMAs ``round_up(B + L, 8)`` window rows
at row ``t·B``; outputs are tiled (B, bn) with N in 128-lane multiples.
A leading batch grid axis serves ``vmap`` (``common.fold_vmap``).

Both ``*_call`` entry points resolve ``interpret=None`` through
``common.default_interpret()`` at call time: compiled Mosaic on a real
TPU, interpret mode elsewhere, overridable via ``REPRO_PALLAS_INTERPRET``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.kernels.common import round_up


def _sptc_kernel(values_ref, meta_ref, x_ref, y_ref, *, k: int):
    vals = values_ref[:]                       # (M, K/2)
    meta = meta_ref[:]                         # (M, K/2) int32
    x = x_ref[:]                               # (K, bn)
    m, kh = vals.shape
    # gather index per compressed slot: 4*segment + 2-bit position
    seg = (jax.lax.broadcasted_iota(jnp.int32, (m, kh), 1) // 2) * 4
    gidx = seg + meta                          # (M, K/2)
    # In-VMEM decompression: scatter values to their K positions via one-hot.
    # K is tiny (= 2L); this is VPU work, the MXU then runs the dense dot.
    kpos = jax.lax.broadcasted_iota(jnp.int32, (m, kh, k), 2)
    onehot = (gidx[:, :, None] == kpos).astype(vals.dtype)
    w = jnp.sum(vals[:, :, None] * onehot, axis=1)          # (M, K)
    y_ref[:] = jnp.dot(w, x, precision=common.dot_precision(x.dtype),
                       preferred_element_type=jnp.float32
                       ).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _sptc_spmm_jit(values, meta, x, *, block_n: int, interpret: bool):
    m, kh = values.shape
    k, n = x.shape
    if kh * 2 != k:
        raise ValueError(f"K/2 mismatch: values {kh} vs x K={k}")
    bn = min(block_n, round_up(n, 128))
    n_pad = round_up(n, bn)
    if n_pad != n:
        x = jnp.pad(x, ((0, 0), (0, n_pad - n)))
    grid = (n_pad // bn,)
    y = pl.pallas_call(
        functools.partial(_sptc_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, kh), lambda i: (0, 0)),     # compressed values
            pl.BlockSpec((m, kh), lambda i: (0, 0)),     # metadata
            pl.BlockSpec((k, bn), lambda i: (0, i)),     # RHS N-tile
        ],
        out_specs=pl.BlockSpec((m, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, n_pad), x.dtype),
        interpret=interpret,
    )(values.astype(x.dtype), meta.astype(jnp.int32), x)
    return y[:, :n]


def sptc_spmm_call(values, meta, x, *, block_n: int = 512,
                   interpret: bool | None = None):
    """y = SpTC(values, meta) @ x.   values/meta: (M, K/2); x: (K, N)."""
    if interpret is None:
        interpret = common.default_interpret()
    return _sptc_spmm_jit(values, meta, x, block_n=block_n,
                          interpret=interpret)


# ---------------------------------------------------------------------------
# v2: fused window-DMA + in-kernel swap/gather + MXU matmul
# ---------------------------------------------------------------------------

def _fused_kernel(x_hbm, vals_ref, meta_ref, y_ref, scratch, sem, *,
                  tiles: int, L: int, rows: int, bn: int, star_fast: bool,
                  compute):
    """One grid step computes ``B`` output rows — ``B // L`` stacked row
    tiles of ``L`` — of batch item ``b`` from a ``rows``-row window
    starting at row ``t·B``.

    ``B`` and ``rows`` are multiples of the sublane tile, so the output
    block, the window DMA and its row offset are all (8, 128)-aligned;
    ``vals_ref`` / ``meta_ref`` hold the operand's L rows repeated per
    stacked tile (``B`` rows).
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    t = pl.program_id(2)
    B, kh = vals_ref.shape

    def dma(slot, tt):
        return pltpu.make_async_copy(
            x_hbm.at[b, pl.ds(pl.multiple_of(tt * B, B), rows),
                     pl.ds(pl.multiple_of(j * bn, bn), bn)],
            scratch.at[slot], sem.at[slot])

    # cross-grid-step double buffering: scratch persists across the
    # sequential row-tile axis (grid iterates it fastest), so tile t+1's
    # window streams from HBM while tile t computes.
    @pl.when(t == 0)
    def _():
        dma(0, 0).start()

    @pl.when(t + 1 < tiles)
    def _():
        dma((t + 1) % 2, t + 1).start()

    dma(t % 2, t).wait()
    win = scratch[t % 2]                     # (rows, bn)
    vals = vals_ref[:]                       # (B, K/2)
    if compute is not None:
        win = win.astype(compute)
        vals = vals.astype(compute)
    vals = vals.astype(jnp.float32)
    if star_fast:
        # banded value layout: row i's slot off reads window row i + off —
        # no metadata, K/2 shifted VPU FMAs with f32 accumulation.
        acc = jnp.zeros((B, bn), dtype=jnp.float32)
        for jj in range(kh):
            acc = acc + vals[:, jj:jj + 1] * \
                win[jj:jj + B, :].astype(jnp.float32)
        y_ref[:] = acc.astype(y_ref.dtype)
        return
    # unpack the 2-bit metadata from the packed words in-register
    words = meta_ref[:]                      # (B, nwords) uint32
    jj = jax.lax.broadcasted_iota(jnp.int32, (B, kh), 1)
    exp = jnp.zeros((B, kh), jnp.uint32)
    for w in range(words.shape[1]):
        exp = jnp.where(jj // 16 == w, words[:, w:w + 1], exp)
    meta = (jax.lax.shift_right_logical(exp, (2 * (jj % 16)).astype(
        jnp.uint32)) & 3).astype(jnp.int32)
    gidx = 4 * (jj // 2) + meta              # swapped-window position
    # strided swap folded into the decompression positions: the swap
    # "odd p exchanges halves" is an involution, so swapped position g
    # reads window row kpos(g) — derived from an iota, zero loads and
    # zero stores (§3.3).  Stacked tile s reads its window from row s·L.
    kpos = jnp.where(gidx % 2 == 1,
                     jnp.where(gidx < L, gidx + L, gidx - L), gidx)
    i = jax.lax.broadcasted_iota(jnp.int32, (B, kh), 0)
    col = (i // L) * L + kpos                # (B, K/2) window column
    q = jax.lax.broadcasted_iota(jnp.int32, (B, rows), 1)
    w_dense = jnp.zeros((B, rows), jnp.float32)
    for s in range(kh):                      # one-hot decompression
        w_dense = w_dense + jnp.where(q == col[:, s:s + 1],
                                      vals[:, s:s + 1], 0.0)
    w_dense = w_dense.astype(win.dtype)
    y_ref[:] = jnp.dot(w_dense, win, precision=common.dot_precision(win.dtype),
                       preferred_element_type=jnp.float32
                       ).astype(y_ref.dtype)


def _fused_geometry(L: int, dtype) -> "tuple[int, int]":
    """(output rows per grid step ``B``, window rows) for a fused call.

    ``B`` is the least multiple of ``L`` that fills whole sublane tiles
    (8 rows of f32, 16 of bf16); the window covers the ``B + L`` rows those
    outputs read, rounded up to the sublane tile.
    """
    sub = 8 * 4 // jnp.dtype(dtype).itemsize
    B = L * sub // math.gcd(L, sub)
    return B, round_up(B + L, sub)


@functools.partial(jax.jit, static_argnames=(
    "n_out", "L", "block_n", "star_fast", "compute_dtype", "interpret"))
def _sptc_fused_jit(x3d, values, meta_bits, *, n_out: int, L: int,
                    block_n: int, star_fast: bool, compute_dtype,
                    interpret: bool):
    nb, rows_in, c = x3d.shape
    B, rows = _fused_geometry(L, x3d.dtype)
    tiles = -(-n_out // B)
    need = (tiles - 1) * B + rows
    bn = min(block_n, round_up(c, 128))
    c_pad = round_up(c, bn)
    if need > rows_in or c_pad != c:
        x3d = jnp.pad(x3d, ((0, 0), (0, max(0, need - rows_in)),
                            (0, c_pad - c)))
    reps = B // L
    values = jnp.tile(values, (reps, 1))
    meta_bits = jnp.tile(meta_bits, (reps, 1))
    compute = jnp.dtype(compute_dtype) if compute_dtype else None
    kern = functools.partial(_fused_kernel, tiles=tiles, L=L, rows=rows,
                             bn=bn, star_fast=star_fast, compute=compute)
    y = pl.pallas_call(
        kern,
        grid=(nb, c_pad // bn, tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),             # input in HBM
            pl.BlockSpec(values.shape, lambda b, j, t: (0, 0)),
            pl.BlockSpec(meta_bits.shape, lambda b, j, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, B, bn), lambda b, j, t: (b, t, j)),
        out_shape=jax.ShapeDtypeStruct((nb, tiles * B, c_pad), x3d.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, rows, bn), x3d.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(x3d, values, meta_bits)
    return y[:, :n_out, :c]


def sptc_fused_call(values, meta_bits, x2d, *, n_out: int, L: int,
                    block_n: int = 512, star_fast: bool = False,
                    compute_dtype: str | None = None,
                    interpret: bool | None = None):
    """Fused stencil SpMM: y[i] = sum_j band(i, j) * x2d[i + ...].

    ``values``    (L, K/2) compressed operand — the banded layout from
                  ``contiguous_band_values`` when ``star_fast=True``.
    ``meta_bits`` (L, ceil(K/32)) packed uint32 metadata words.
    ``x2d``       (>= n_out + L, C) input rows, UNswapped — the swap
                  happens inside the kernel.
    Returns the (n_out, C) stencil output.  ``vmap`` over ``x2d`` runs as
    the kernel's own batch grid axis (``common.fold_vmap``).
    """
    if interpret is None:
        interpret = common.default_interpret()
    call = functools.partial(_sptc_fused_jit, n_out=n_out, L=L,
                             block_n=block_n, star_fast=star_fast,
                             compute_dtype=compute_dtype, interpret=interpret)
    return common.fold_vmap(call)(x2d, values, meta_bits)
