"""Jitted public wrappers for the 2:4 compressed SpMM kernels."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparsify import (Sparse24, contiguous_band_values,
                                 strided_swap_perm)
from repro.kernels.sptc_spmm.kernel import (sptc_fused_call, sptc_planes_call,
                                            sptc_spmm_call)


def sptc_spmm(values, meta, x, *, block_n: int = 512,
              interpret: bool | None = None):
    """Compressed (M, K/2) x (K, N) -> (M, N)."""
    return sptc_spmm_call(jnp.asarray(values), jnp.asarray(meta),
                          jnp.asarray(x), block_n=block_n,
                          interpret=interpret)


def sptc_spmm_windows(values, meta, windows, *, block_n: int = 512,
                      interpret: bool | None = None):
    """Batched over the leading tile axis: (T, K, N) -> (T, M, N).

    vmap adds the tile axis as an outer grid dimension of the pallas_call.
    """
    values = jnp.asarray(values)
    meta = jnp.asarray(meta)
    fn = lambda w: sptc_spmm_call(values, meta, w, block_n=block_n,
                                  interpret=interpret)
    return jax.vmap(fn)(jnp.asarray(windows))


def _check_perm(perm, L: int, who: str) -> None:
    if not np.array_equal(np.asarray(perm), strided_swap_perm(L)):
        raise ValueError(
            f"{who} requires the strided-swap permutation — the kernel "
            "derives it in closed form from an iota (§3.3)")


def sptc_spmm_fused(operand: Sparse24, perm, x2d, *, n_out: int, L: int,
                    star_fast: "bool | str" = "auto",
                    compute_dtype: Optional[str] = None,
                    interpret: bool | None = None):
    """One fused Pallas program: window DMA → in-kernel swap+gather → MXU.

    ``x2d`` is the raw (n_out + 2r, C) haloed input — NOT windowed, NOT
    swapped; the kernel folds both into its load addressing (§3.3).  All
    tables (compressed values, packed meta words, the fast-path banded
    layout) are computed here in NumPy at trace time, so under ``jax.jit``
    they are compile-time constants: slight compile time, zero runtime.

    ``star_fast``: ``"auto"`` uses the metadata-free banded path whenever
    the swap∘meta gather is the identity band of the taps; ``True``
    requires it (ValueError if the operand's pattern escapes the band);
    ``False`` always runs the faithful one-hot decompression.
    """
    perm = np.asarray(perm)
    _check_perm(perm, L, "sptc_spmm_fused")
    fast_vals = (contiguous_band_values(operand, perm)
                 if star_fast in ("auto", True) else None)
    if star_fast is True and fast_vals is None:
        raise ValueError("operand's 2:4 pattern is not the identity band "
                         "of the taps; star fast path unavailable")
    x2d = jnp.asarray(x2d)
    meta_bits = jnp.asarray(operand.meta_bits())
    vals = np.asarray(fast_vals if fast_vals is not None
                      else operand.values)
    return sptc_fused_call(
        jnp.asarray(vals, dtype=x2d.dtype), meta_bits, x2d,
        n_out=n_out, L=L,
        star_fast=fast_vals is not None,
        compute_dtype=compute_dtype, interpret=interpret)


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
