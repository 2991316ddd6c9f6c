"""StencilEngine — a generic interpreter for :class:`~repro.core.ir.LoweredPlan`.

The engine no longer hard-codes the paper's transform: ``transform.lower_spec``
runs the ahead-of-time pipeline (row-decompose → kernel-matrix → strided-swap
2:4 sparsify → gather schedule → backend emit) and returns an explicit,
inspectable ``LoweredPlan``; this module merely *executes* that IR — every
table (kernel matrices, compressed operands, window orders, slot/tap
schedules) is read from the plan, never recomputed here.

Backends (all mathematically equivalent; cross-checked in tests):
  direct      pure-jnp shifted multiply-add — the semantic oracle.
  gemm        dense kernel-matrix GEMM (generalized TCStencil, paper §3.2.1):
              banded (L, 2L) matrix times 2L-row input windows.
  sptc        simulated Sparse Tensor Core execution: strided-swap permuted
              + 2:4-compressed kernel, row-swapped inputs (paper §3.2.2/§3.3).
  pallas_*    Pallas TPU kernels (see repro.kernels), same math.  A
              ``pallas_sptc`` row op runs the star kernel (``sptc_star``)
              in a ``single`` or ``star-axis`` plan and the one-hot kernel
              (``sptc_onehot``) otherwise: the plan's mode decides, in
              ``_apply_op`` alone.

Two workload classes ride on IR-level attributes:
  * variable coefficients (``coefficients=`` on the engine): per-output-point
    weight values applied through ONE shared 2:4 pattern — the swap
    permutation and gather tables come straight from the plan, computed once.
  * temporal blocking (``temporal_steps=k``): one compiled function applies
    the stencil ``k`` times; the input carries a ``k·r`` halo that shrinks by
    ``r`` per step, and ``iterate`` advances ``k`` steps per scan iteration.

Input convention: ``x`` carries the halo — shape (N1+2kr, ..., Nd+2kr) for a
k-step engine — and the output is the (N1, ..., Nd) interior update.  A
``"planes"`` plan (3-D, radius 1, on ``pallas_sptc``) steps the
halo-inclusive state in place: its one kernel a step writes the next state,
zero halo included, so ``iterate`` carries it with no crop, re-pad or
relayout.  Planes too wide for the kernel's VMEM run its row ops one by one
and re-pad, as a ``"rows"`` plan does.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scopes
from repro.core.ir import (BACKENDS, LoweredPlan, RowOp,
                           SegmentGatherSchedule)
from repro.core.sparsify import Sparse24, decode_24
from repro.core.stencil import StencilSpec
from repro.core.transform import lower_spec

__all__ = ["BACKENDS", "StencilEngine", "apply_stencil"]

ApplyFn = Callable[[jnp.ndarray], jnp.ndarray]

#: f32 operands contract in f32: TPU's default f32 dot is a single bf16
#: pass, which would drift from the f32 ``direct`` oracle.
_F32_DOT = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# 1-D application primitives (stencil axis leading, free axis trailing).
# Each reads its tables as arguments — the interpreter feeds them from the
# plan.
# ---------------------------------------------------------------------------

def _windows(x2d: jnp.ndarray, n_out: int, L: int,
             order: Optional[np.ndarray] = None
             ) -> Tuple[jnp.ndarray, int]:
    """Overlapping (ntiles, 2L, C) windows of a (rows, C) input.

    Tile t covers outputs [tL, tL+L) and reads input rows [tL, tL+2L).
    Rows are zero-padded so every window is in-bounds; the pad rows only ever
    multiply structurally-zero kernel-matrix columns.

    ``order`` reorders the rows *within* each window by folding the
    permutation into the gather's load addresses (paper §3.3: the input row
    swap is zero-cost — it must not lower to a separate permute/gather op).
    """
    x2d, ntiles = _pad_tiles(x2d, n_out, L)
    within = np.arange(2 * L) if order is None else np.asarray(order)
    idx = (np.arange(ntiles) * L)[:, None] + within[None, :]
    with jax.named_scope(scopes.KERNEL_PREP):
        return x2d[jnp.asarray(idx)], ntiles


def _pad_tiles(x2d: jnp.ndarray, n_out: int, L: int
               ) -> Tuple[jnp.ndarray, int]:
    """Zero-pad the row axis so ``ntiles`` full tile reads are in-bounds."""
    ntiles = -(-n_out // L)
    need = (ntiles + 1) * L
    with jax.named_scope(scopes.KERNEL_PREP):
        x2d = jnp.pad(x2d, ((0, max(0, need - x2d.shape[0])), (0, 0)))
    return x2d, ntiles


def _op_direct(w: np.ndarray, x2d: jnp.ndarray, n_out: int) -> jnp.ndarray:
    taps = w.shape[0]
    acc = jnp.zeros((n_out, x2d.shape[1]), dtype=x2d.dtype)
    for k in range(taps):
        if w[k] != 0:
            acc = acc + jnp.asarray(w[k], dtype=x2d.dtype) * x2d[k:k + n_out]
    return acc


def _op_gemm(K: np.ndarray, x2d: jnp.ndarray, n_out: int,
             L: int) -> jnp.ndarray:
    Km = jnp.asarray(K, dtype=x2d.dtype)
    win, ntiles = _windows(x2d, n_out, L)
    y = jnp.einsum("lk,tkc->tlc", Km, win,
                   preferred_element_type=jnp.float32,
                   precision=_F32_DOT).astype(x2d.dtype)
    return y.reshape(ntiles * L, -1)[:n_out]


def _op_sptc(values: np.ndarray, comb: np.ndarray, x2d: jnp.ndarray,
             n_out: int, L: int) -> jnp.ndarray:
    """Compressed 2:4 SpMM with the row swap folded into load addressing.

    ``comb[m, j] = perm[4*seg(j) + meta[m, j]]`` — the plan's gather-schedule
    slots.  The strided-swap permutation AND the 2-bit metadata gather are
    both static, so they compose into the window gather's index array at
    trace time: the lowered hot path contains exactly ONE gather (the im2col
    window read, same as the dense gemm path) and no stray permute ops —
    the paper's §3.3 zero-runtime-overhead contract, certified ahead of
    time by ``repro.vet``'s lowering analyzer.  Numerically identical to
    ``sptc.sptc_matmul`` over swapped windows (the tier-1 oracle tests).
    """
    x2d, ntiles = _pad_tiles(x2d, n_out, L)
    rows = (np.arange(ntiles) * L)[:, None, None] + comb[None, :, :]
    xg = x2d[jnp.asarray(rows)]                                 # (T, L, K/2, C)
    vals = jnp.asarray(values, dtype=x2d.dtype)
    y = jnp.einsum("mk,tmkc->tmc", vals, xg,
                   preferred_element_type=jnp.float32,
                   precision=_F32_DOT).astype(x2d.dtype)
    return y.reshape(ntiles * L, -1)[:n_out]


def _op_pallas_mxu(K: np.ndarray, x2d: jnp.ndarray, n_out: int,
                   L: int) -> jnp.ndarray:
    from repro.kernels.stencil_gemm.ops import windows_gemm
    Km = jnp.asarray(K, dtype=x2d.dtype)
    win, ntiles = _windows(x2d, n_out, L)
    y = windows_gemm(Km, win)
    return y.reshape(ntiles * L, -1)[:n_out]


def _op_pallas_sptc(operand: Sparse24, perm: np.ndarray, x2d: jnp.ndarray,
                    n_out: int, L: int, star_fast: bool) -> jnp.ndarray:
    """ONE fused Pallas program: window DMA, then the star kernel's
    banded FMAs (``star_fast``) or the one-hot kernel's in-kernel
    swap+segment gather and MXU matmul.  Nothing is windowed, permuted,
    or gathered outside the kernel (§3.3 zero runtime overhead; certified
    by ``repro.vet``'s pallas-fused analyzer)."""
    from repro.kernels.sptc_spmm.ops import sptc_spmm_fused
    return sptc_spmm_fused(operand, perm, x2d, n_out=n_out, L=L,
                           star_fast=star_fast)


# ---------------------------------------------------------------------------
# Variable-coefficient values: trace-time constants built from the plan's
# slot/tap schedule — computed once per engine, shared 2:4 pattern.
# ---------------------------------------------------------------------------

def _values_tensor(w2d: np.ndarray, tap_tbl: np.ndarray, ntiles: int,
                   L: int, n_out: int) -> np.ndarray:
    """Per-slot value tensor (T, L, S, C) for one variable-coefficient op.

    ``w2d`` is the op's value slab rearranged output-major, shape
    ``(n_out, C, taps)``; ``tap_tbl`` the plan's (L, S) tap schedule.  Slot
    ``(t, l, s)`` of output row ``i = tL + l`` multiplies ``w2d[i, :,
    tap_tbl[l, s]]`` — zero where the slot is structurally dead (tap -1) or
    the row is tile padding.
    """
    gi = (np.arange(ntiles) * L)[:, None] + np.arange(L)[None, :]   # (T, L)
    valid = gi < n_out
    gi = np.minimum(gi, n_out - 1)
    tap_ok = tap_tbl >= 0
    tap_c = np.where(tap_ok, tap_tbl, 0)
    V = w2d[gi[:, :, None], :, tap_c[None, :, :]]                # (T, L, S, C)
    mask = (tap_ok[None, :, :] & valid[:, :, None])[..., None]
    return np.where(mask, V, np.zeros((), dtype=w2d.dtype))


def _op_var_direct(w2d: np.ndarray, x2d: jnp.ndarray,
                   n_out: int) -> jnp.ndarray:
    taps = w2d.shape[-1]
    acc = jnp.zeros((n_out, x2d.shape[1]), dtype=x2d.dtype)
    for k in range(taps):
        if np.any(w2d[:, :, k]):
            wk = jnp.asarray(w2d[:, :, k], dtype=x2d.dtype)
            acc = acc + wk * x2d[k:k + n_out]
    return acc


def _op_var_gemm(w2d: np.ndarray, gather: SegmentGatherSchedule, operand: int,
                 x2d: jnp.ndarray, n_out: int, L: int) -> jnp.ndarray:
    win, ntiles = _windows(x2d, n_out, L)
    V = _values_tensor(w2d, gather.taps[operand], ntiles, L, n_out)
    y = jnp.einsum("tlsc,tsc->tlc", jnp.asarray(V, dtype=x2d.dtype), win,
                   preferred_element_type=jnp.float32,
                   precision=_F32_DOT).astype(x2d.dtype)
    return y.reshape(ntiles * L, -1)[:n_out]


def _op_var_sptc(w2d: np.ndarray, gather: SegmentGatherSchedule, operand: int,
                 x2d: jnp.ndarray, n_out: int, L: int) -> jnp.ndarray:
    comb = gather.slots[operand]                  # perm ∘ meta, compile-time
    x2d, ntiles = _pad_tiles(x2d, n_out, L)
    rows = (np.arange(ntiles) * L)[:, None, None] + comb[None, :, :]
    xg = x2d[jnp.asarray(rows)]                                 # (T, L, K/2, C)
    V = _values_tensor(w2d, gather.taps[operand], ntiles, L, n_out)
    y = jnp.einsum("tmsc,tmsc->tmc", jnp.asarray(V, dtype=x2d.dtype), xg,
                   preferred_element_type=jnp.float32,
                   precision=_F32_DOT).astype(x2d.dtype)
    return y.reshape(ntiles * L, -1)[:n_out]


# ---------------------------------------------------------------------------
# The stage interpreter: LoweredPlan -> traced jnp program.
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.LAYOUT)
def _apply_op(plan: LoweredPlan, op: RowOp, x: jnp.ndarray, n_out: int,
              axis: int) -> jnp.ndarray:
    """Execute one constant-coefficient RowOp from the plan's tables.

    All of it but the kernel wrappers' own copies counts as the axis move
    around the kernel: the layout copies the compiler makes for a kernel's
    fixed operand layout carry the kernel call's scope.
    """
    x = jnp.moveaxis(x, axis, 0)
    rest = x.shape[1:]
    x2d = x.reshape(x.shape[0], -1)
    backend, L, i = plan.emit.backend, plan.L, op.operand
    if backend == "direct":
        y = _op_direct(plan.decompose.kernels[i], x2d, n_out)
    elif backend == "gemm":
        kern = plan.kernel
        assert kern is not None
        y = _op_gemm(kern.matrices[i], x2d, n_out, L)
    elif backend == "sptc":
        sp, gather = plan.sparsify, plan.gather
        assert sp is not None and gather is not None
        y = _op_sptc(sp.operands[i].values, gather.slots[i], x2d, n_out, L)
    elif backend == "pallas_mxu":
        kern = plan.kernel
        assert kern is not None
        y = _op_pallas_mxu(kern.matrices[i], x2d, n_out, L)
    elif backend == "pallas_sptc":
        sp = plan.sparsify
        assert sp is not None
        # the metadata-free banded path is the star decomposition's fast
        # path; box "rows" ops keep the faithful one-hot decompression
        star = plan.decompose.mode in ("single", "star-axis")
        y = _op_pallas_sptc(sp.operands[i], sp.perm, x2d, n_out, L,
                            star_fast=star)
    else:
        raise ValueError(f"unknown 1-D backend {backend}")
    return jnp.moveaxis(y.reshape((n_out,) + rest), 0, axis)


def _op_slice(mode: str, op: RowOp, out_shape: Tuple[int, ...], r: int,
              d: int) -> Tuple[Tuple[slice, ...], int]:
    """(input slice, stencil axis) for one RowOp of a d-D application."""
    if mode == "single":
        return (slice(None),), 0
    if mode == "star-axis":
        sl = tuple(slice(None) if a == op.axis else slice(r, r + out_shape[a])
                   for a in range(d))
        return sl, op.axis
    # rows and planes: ``lead`` offsets every axis but the op's own
    lead = list(op.lead)
    lead.insert(op.axis, None)
    sl = tuple(slice(None) if u is None else slice(u, u + out_shape[a])
               for a, u in enumerate(lead))
    return sl, op.axis


def _emit_const(plan: LoweredPlan) -> ApplyFn:
    """Constant-coefficient single/star-axis/rows emission — shape-generic."""
    r, d = plan.spec.radius, plan.spec.ndim
    dec = plan.decompose
    mode = dec.mode

    if mode == "single":
        op0 = dec.ops[0]

        def fn1(x: jnp.ndarray) -> jnp.ndarray:
            n_out = x.shape[0] - 2 * r
            return _apply_op(plan, op0, x, n_out, 0)
        return fn1

    @jax.named_scope(scopes.ACCUMULATE)
    def fn(x: jnp.ndarray) -> jnp.ndarray:
        out_shape = tuple(s - 2 * r for s in x.shape)
        acc = jnp.zeros(out_shape, dtype=x.dtype)
        for op in dec.ops:
            sl, axis = _op_slice(mode, op, out_shape, r, d)
            acc = acc + _apply_op(plan, op, x[sl], out_shape[axis], axis)
        return acc
    return fn


def _emit_fused_2d(plan: LoweredPlan) -> ApplyFn:
    """§Perf D emission: ONE window gather + ONE stacked GEMM for all
    2r+1 kernel rows of a 2-D stencil (vs 2r+1 of each).

    Every row kernel sees the same last-axis window structure; only the
    leading-axis slice differs.  So gather windows of the FULL input once,
    multiply by the (R·L, 2L) concatenation of the plan's per-row operands
    (R = #rows), then accumulate each row's result from a shifted column
    slice.  Same MACs, ~R× fewer gathers/dispatches and one MXU-friendly
    tall GEMM.  On the sptc path the stacked matrix is the dense decode of
    the 2:4-compressed operands — the fused GEMM computes exactly what R
    sptc SpMM calls do — and the strided swap rides the window gather's
    load order (§3.3).
    """
    r, L = plan.spec.radius, plan.L
    dec, sp = plan.decompose, plan.sparsify
    R = len(dec.ops)
    if sp is not None:
        mats = [decode_24(opnd) for opnd in sp.operands]
        order: Optional[np.ndarray] = sp.perm
    else:
        kern = plan.kernel
        assert kern is not None
        mats = [np.asarray(m) for m in kern.matrices]
        order = None
    K_all = np.concatenate(mats, axis=0)          # (R*L, 2L)
    leads = [int(op.lead[0]) for op in dec.ops]

    @jax.named_scope(scopes.ACCUMULATE)
    def fn(x: jnp.ndarray) -> jnp.ndarray:
        h_in = x.shape[0]
        h_out = h_in - 2 * r
        w_out = x.shape[1] - 2 * r
        with jax.named_scope(scopes.LAYOUT):
            xt = x.T                               # (W+2r, H+2r)
        # zero-cost row swap: perm folds into the window gather (§3.3)
        win, ntiles = _windows(xt, w_out, L, order=order)  # (T, 2L, H+2r)
        Km = jnp.asarray(K_all, dtype=x.dtype)
        y = jnp.einsum("lk,tkc->tlc", Km, win,
                       preferred_element_type=jnp.float32,
                       precision=_F32_DOT
                       ).astype(x.dtype)           # (T, R*L, H+2r)
        with jax.named_scope(scopes.LAYOUT):
            y = y.reshape(ntiles, R, L, h_in)
            yr = y.transpose(1, 0, 2, 3).reshape(R, ntiles * L, h_in)
        acc = jnp.zeros((w_out, h_out), dtype=x.dtype)
        for i, u in enumerate(leads):
            acc = acc + yr[i, :w_out, u:u + h_out]
        with jax.named_scope(scopes.LAYOUT):
            return acc.T
    return fn


def _var_slab_2d(slab: np.ndarray, axis: int) -> np.ndarray:
    """Rearrange a value slab output-major: (n_out, C, taps) matching the
    (stencil-axis leading, free axis trailing) layout of ``_apply_op``."""
    w = np.moveaxis(slab, axis, 0)
    return np.ascontiguousarray(w.reshape(w.shape[0], -1, slab.shape[-1]))


def _emit_var(plan: LoweredPlan) -> ApplyFn:
    """Variable-coefficient emission — fixed-shape by construction.

    The coefficient field pins the output shape, so every table (including
    the per-slot value tensors) is a trace-time constant; the shared 2:4
    pattern means ONE slot/tap schedule serves every operand.
    """
    r, d = plan.spec.radius, plan.spec.ndim
    dec, gather = plan.decompose, plan.gather
    mode, L = dec.mode, plan.L
    assert dec.coefficients is not None
    out_shape = dec.coefficients[0].shape[:-1]
    in_shape = tuple(s + 2 * r for s in out_shape)
    backend = plan.emit.backend

    per_op: List[Tuple[RowOp, Tuple[slice, ...], int, np.ndarray]] = []
    for op in dec.ops:
        sl, axis = _op_slice(mode, op, out_shape, r, d)
        w2d = _var_slab_2d(dec.coefficients[op.operand], axis)
        per_op.append((op, sl, axis, w2d))

    @jax.named_scope(scopes.ACCUMULATE)
    def fn(x: jnp.ndarray) -> jnp.ndarray:
        if tuple(x.shape) != in_shape:
            raise ValueError(
                f"variable-coefficient engine is fixed-shape: expected "
                f"input {in_shape} (= out {out_shape} + 2r halo), got "
                f"{tuple(x.shape)}")
        acc = jnp.zeros(out_shape, dtype=x.dtype)
        for op, sl, axis, w2d in per_op:
            xs = x[sl]
            with jax.named_scope(scopes.LAYOUT):
                xs = jnp.moveaxis(xs, axis, 0)
                rest = xs.shape[1:]
                x2d = xs.reshape(xs.shape[0], -1)
            n_out = out_shape[axis]
            if backend == "direct":
                y2d = _op_var_direct(w2d, x2d, n_out)
            elif backend == "gemm":
                assert gather is not None
                y2d = _op_var_gemm(w2d, gather, op.operand, x2d, n_out, L)
            elif backend == "sptc":
                assert gather is not None
                y2d = _op_var_sptc(w2d, gather, op.operand, x2d, n_out, L)
            else:
                raise ValueError(
                    f"variable coefficients unsupported on {backend}")
            with jax.named_scope(scopes.LAYOUT):
                y = jnp.moveaxis(y2d.reshape((n_out,) + rest), 0, axis)
            acc = acc + y
        return acc
    return fn


def _emit_planes(plan: LoweredPlan, halo: bool) -> ApplyFn:
    """``"planes"`` emission: with ``halo``, the halo-inclusive state to
    the next one (zero halo); else the interior update.

    Where the state's planes fit the kernel's VMEM, one ``sptc_onehot3d``
    program runs every row op of the step from one window of the state
    and writes the whole next state, zero halo and all.  Wider planes run
    the same row ops one by one along ``y`` (the ``rows`` emission).
    """
    from repro.kernels.sptc_spmm.kernel import planes_fit
    from repro.kernels.sptc_spmm.ops import sptc_planes
    sp = plan.sparsify
    assert sp is not None
    ops = plan.decompose.ops
    operands = [sp.operands[op.operand] for op in ops]
    leads = [op.lead for op in ops]
    r = plan.spec.radius
    one_by_one = _emit_const(plan)

    def fn(x: jnp.ndarray) -> jnp.ndarray:
        if not planes_fit(x.shape, x.dtype, r, len(ops)):
            y = one_by_one(x)
            if not halo:
                return y
            with jax.named_scope(scopes.REPAD):
                return jnp.pad(y, r)
        y = sptc_planes(operands, sp.perm, x, leads=leads, L=plan.L, r=r)
        if halo:
            return y
        with jax.named_scope(scopes.KERNEL_PREP):
            return y[r:-r, r:-r, r:-r]
    return fn


def emit_halo_step(plan: LoweredPlan) -> Optional[ApplyFn]:
    """The plan's step from one halo-inclusive state to the next (zero
    halo), for plans that step the state in place; else ``None``."""
    if plan.decompose.mode != "planes":
        return None
    plan.validate()
    return _emit_planes(plan, halo=True)


def _emit_step(plan: LoweredPlan) -> ApplyFn:
    """One stencil application from the plan's tables (temporal_steps ignored)."""
    if plan.decompose.mode == "planes":
        return _emit_planes(plan, halo=False)
    if plan.emit.backend == "pallas_direct":
        from repro.kernels import dispatch as kdispatch
        fn: ApplyFn = kdispatch.build(plan.spec, plan.emit.backend, plan.L)
        return fn
    if plan.emit.coefficient_mode == "var":
        return _emit_var(plan)
    if plan.decompose.mode == "fused-rows":
        return _emit_fused_2d(plan)
    return _emit_const(plan)


def emit(plan: LoweredPlan) -> ApplyFn:
    """LoweredPlan -> executable (untraced) function — the interpreter.

    A temporal-blocked plan unrolls ``k`` applications into one program:
    the halo shrinks by ``r`` per step, so a ``k·r``-halo input yields the
    interior update after ``k`` steps — ``k`` dots and one window gather per
    step on the matrix backends, nothing else (§3.3 preserved per step).
    """
    plan.validate()
    step = _emit_step(plan)
    k = plan.emit.temporal_steps
    if k == 1:
        return step

    def fn(x: jnp.ndarray) -> jnp.ndarray:
        for _ in range(k):
            x = step(x)
        return x
    return fn


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class StencilEngine:
    """Compiled applicator for one StencilSpec — lowers, then interprets."""

    def __init__(self, spec: StencilSpec, backend: str = "direct",
                 L: Optional[int] = None, fuse_rows: bool = False,
                 temporal_steps: int = 1,
                 coefficients: Optional[np.ndarray] = None) -> None:
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}")
        self.plan_ir: LoweredPlan = lower_spec(
            spec, backend=backend, L=L, fuse_rows=fuse_rows,
            temporal_steps=temporal_steps, coefficients=coefficients)
        self.spec = spec
        self.backend = backend
        self.L = self.plan_ir.L
        # §Perf D: one window-gather + one stacked GEMM for all kernel rows
        self.fuse_rows = fuse_rows
        self.temporal_steps = temporal_steps
        self._fn = jax.jit(emit(self.plan_ir))
        halo_step = emit_halo_step(self.plan_ir)
        self._halo_step = None if halo_step is None else jax.jit(halo_step)

    # -- public API ----------------------------------------------------------
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return self._fn(x)

    def iterate(self, x: jnp.ndarray, steps: int) -> jnp.ndarray:
        """Iterative (Jacobi-style) application with zero-halo re-padding.

        A temporal-blocked engine advances ``k`` steps per scan iteration
        (``x`` then carries the ``k·r`` halo); ``steps`` must be a multiple
        of ``k``.
        """
        k = self.temporal_steps
        if steps % k != 0:
            raise ValueError(
                f"steps={steps} must be a multiple of temporal_steps={k}")
        if self._halo_step is not None:
            step = self._halo_step

            def in_place(x_in: jnp.ndarray, _: None
                         ) -> Tuple[jnp.ndarray, None]:
                return step(x_in), None

            # two steps a loop iteration: the second writes into the
            # buffer the first read, so the loop carries its state
            # without a copy
            out, _ = jax.lax.scan(in_place, x, None, length=steps, unroll=2)
            return out
        pad = [(k * self.spec.radius,) * 2] * self.spec.ndim

        def body(x_in: jnp.ndarray, _: None) -> Tuple[jnp.ndarray, None]:
            y = self._fn(x_in)
            with jax.named_scope(scopes.REPAD):
                return jnp.pad(y, pad), None

        out, _ = jax.lax.scan(body, x, None, length=steps // k)
        return out


def apply_stencil(spec: StencilSpec, x: jnp.ndarray, backend: str = "direct",
                  L: Optional[int] = None, temporal_steps: int = 1,
                  coefficients: Optional[np.ndarray] = None) -> jnp.ndarray:
    """One-shot functional entry point, engine-cached by stencil content.

    Repeated calls with the same (spec, backend, L, temporal_steps,
    coefficients) reuse one compiled StencilEngine from the process-wide
    ``repro.tuner`` cache instead of re-building and re-jitting — SPIDER's
    zero-runtime-overhead contract.  For measured backend/L selection use
    :func:`repro.tuner.tuned_apply`.
    """
    from repro.tuner.cache import default_cache
    from repro.tuner.plan import Plan
    plan = Plan.default(spec, backend, L, temporal_steps=temporal_steps)
    return default_cache().engine(spec, plan, coefficients=coefficients)(x)
