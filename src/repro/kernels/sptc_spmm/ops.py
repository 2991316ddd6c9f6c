"""Public wrappers for the fused 2:4 SpTC stencil kernels: they build the
packed tables in NumPy at trace time and call ``kernel``."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.sparsify import (Sparse24, contiguous_band_values,
                                 strided_swap_perm)
from repro.kernels.sptc_spmm.kernel import sptc_fused_call, sptc_planes_call


def _check_perm(perm, L: int, who: str) -> None:
    if not np.array_equal(np.asarray(perm), strided_swap_perm(L)):
        raise ValueError(
            f"{who} requires the strided-swap permutation — the kernel "
            "derives it in closed form from an iota (§3.3)")


def sptc_spmm_fused(operand: Sparse24, perm, x2d, *, n_out: int, L: int,
                    star_fast: bool,
                    compute_dtype: Optional[str] = None,
                    interpret: bool | None = None):
    """One fused Pallas program: window DMA → in-kernel swap+gather → MXU.

    ``x2d`` is the raw (n_out + 2r, C) haloed input — NOT windowed, NOT
    swapped; the kernel folds both into its load addressing (§3.3).  All
    tables (compressed values, packed meta words, the star kernel's banded
    layout) are computed here in NumPy at trace time, so under ``jax.jit``
    they are compile-time constants: slight compile time, zero runtime.

    ``star_fast`` names the kernel: ``True`` runs the metadata-free star
    kernel on the operand's banded values (``contiguous_band_values``;
    ValueError if its swap∘meta gather is not the identity band of the
    taps), ``False`` the faithful one-hot decompression.
    """
    perm = np.asarray(perm)
    _check_perm(perm, L, "sptc_spmm_fused")
    vals = operand.values
    if star_fast:
        vals = contiguous_band_values(operand, perm)
        if vals is None:
            raise ValueError("operand's 2:4 pattern is not the identity band "
                             "of the taps; the star kernel cannot run it")
    x2d = jnp.asarray(x2d)
    meta_bits = jnp.asarray(operand.meta_bits())
    return sptc_fused_call(
        jnp.asarray(np.asarray(vals), dtype=x2d.dtype), meta_bits, x2d,
        n_out=n_out, L=L, star_fast=star_fast, compute_dtype=compute_dtype,
        interpret=interpret)


def sptc_planes(operands: Sequence[Sparse24], perm, x,
                *, leads: Sequence[Tuple[int, int]], L: int, r: int,
                interpret: bool | None = None):
    """One fused Pallas program a step of a 3-D stencil: every row op's
    one-hot band on the MXU, in the state's own halo-inclusive layout.

    ``operands[i]`` is the 2:4 operand of the row op with lead
    ``leads[i] = (dz, dx)``: its 1-D kernel runs along ``y`` at those
    ``z`` and ``x`` offsets.  The packed tables are built here in NumPy at
    trace time.  Returns the next state, shaped as ``x``, zero halo.
    """
    _check_perm(perm, L, "sptc_planes")
    x = jnp.asarray(x)
    values = np.concatenate([np.asarray(op.values) for op in operands])
    meta_bits = np.concatenate([op.meta_bits() for op in operands])
    return sptc_planes_call(
        jnp.asarray(values, dtype=x.dtype), jnp.asarray(meta_bits), x,
        leads=tuple((int(a), int(c)) for a, c in leads), L=L, r=r,
        interpret=interpret)
