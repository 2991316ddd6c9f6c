"""LoweredPlan IR: stage structure, validation, and lower_spec's pipeline.

The tentpole contract of the lowering refactor: `lower_spec` produces an
explicit, ordered, inspectable plan; the engine merely executes it.  These
tests pin the stage sequences per backend family, the table shapes, the
structural validator, and the restrictions on variable-coefficient /
temporal-blocked plans.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import ir
from repro.core.stencil import make_stencil, star_mask
from repro.core.transform import (lower_spec, validate_coefficients)


def _coeff(spec, out_shape, seed=0):
    rng = np.random.default_rng(seed)
    taps = 2 * spec.radius + 1
    c = rng.normal(size=out_shape + (taps,) * spec.ndim)
    if spec.shape == "star":
        c[..., ~star_mask(spec.ndim, spec.radius)] = 0.0
    return c


# ---------------------------------------------------------------------------
# stage sequences per backend family
# ---------------------------------------------------------------------------

def test_stage_sequence_per_backend_family():
    spec = make_stencil("star", 2, 2, seed=0)
    assert lower_spec(spec, "direct").stage_names() == (
        "row-decompose", "emit")
    assert lower_spec(spec, "gemm").stage_names() == (
        "row-decompose", "kernel-matrix", "gather-schedule", "emit")
    assert lower_spec(spec, "sptc").stage_names() == (
        "row-decompose", "kernel-matrix", "strided-swap",
        "gather-schedule", "emit")


def test_stage_names_follow_canonical_order():
    for backend in ("direct", "gemm", "sptc"):
        plan = lower_spec(make_stencil("box", 2, 1, seed=1), backend)
        idx = [ir.STAGE_ORDER.index(n) for n in plan.stage_names()]
        assert idx == sorted(idx)
        assert plan.stage_names()[0] == "row-decompose"
        assert plan.stage_names()[-1] == "emit"


@pytest.mark.parametrize("shape,ndim,r,mode,n_ops", [
    ("box", 1, 2, "single", 1),
    ("star", 2, 1, "star-axis", 2),
    ("star", 3, 2, "star-axis", 3),
    ("box", 2, 1, "rows", 3),
])
def test_decompose_mode_and_op_count(shape, ndim, r, mode, n_ops):
    plan = lower_spec(make_stencil(shape, ndim, r, seed=2), "sptc")
    assert plan.decompose.mode == mode
    assert len(plan.decompose.ops) == n_ops
    assert plan.n_applications() == n_ops
    # downstream tables are per-operand
    assert len(plan.kernel.matrices) == n_ops
    assert len(plan.sparsify.operands) == n_ops
    assert len(plan.gather.slots) == n_ops


def test_fused_rows_mode_and_single_application():
    spec = make_stencil("box", 2, 1, seed=3)
    plan = lower_spec(spec, "sptc", fuse_rows=True)
    assert plan.decompose.mode == "fused-rows"
    assert len(plan.decompose.ops) == 3
    assert plan.n_applications() == 1          # one stacked GEMM
    # the fused window gather carries the swap permutation (§3.3)
    np.testing.assert_array_equal(plan.gather.window, plan.sparsify.perm)


def test_unfused_window_is_identity():
    plan = lower_spec(make_stencil("box", 2, 1, seed=3), "sptc")
    np.testing.assert_array_equal(plan.gather.window,
                                  np.arange(2 * plan.L))


# ---------------------------------------------------------------------------
# table structure
# ---------------------------------------------------------------------------

def test_matrix_and_schedule_shapes():
    spec = make_stencil("box", 1, 2, seed=4)
    plan = lower_spec(spec, "sptc")
    L = plan.L
    assert L == 2 * spec.radius + 2
    (mat,) = plan.kernel.matrices
    assert mat.shape == (L, 2 * L)
    (sp24,) = plan.sparsify.operands
    assert sp24.values.shape == (L, L)          # K/2 = 2L/2 = L slots
    (slots,) = plan.gather.slots
    (taps,) = plan.gather.taps
    assert slots.shape == taps.shape == (L, L)
    assert slots.min() >= 0 and slots.max() < 2 * L


def test_tap_table_masks_off_band():
    # row i, slot column j: tap = j - i inside [0, taps), else -1
    slots = np.tile(np.arange(6), (3, 1))
    t = ir.tap_table(slots, taps=3)
    assert t.shape == (3, 6)
    assert t[0, 0] == 0 and t[0, 2] == 2 and t[0, 3] == -1
    assert t[2, 1] == -1 and t[2, 2] == 0 and t[2, 4] == 2
    assert np.all((t == -1) | ((t >= 0) & (t < 3)))


def test_sparsify_stage_perm_is_involution():
    plan = lower_spec(make_stencil("star", 2, 3, seed=5), "sptc")
    perm = plan.sparsify.perm
    np.testing.assert_array_equal(perm[perm], np.arange(2 * plan.L))


# ---------------------------------------------------------------------------
# variable-coefficient plans
# ---------------------------------------------------------------------------

def test_var_plan_shares_one_pattern():
    spec = make_stencil("box", 2, 1, seed=6)
    c = _coeff(spec, (9, 11))
    plan = lower_spec(spec, "sptc", coefficients=c)
    assert plan.emit.coefficient_mode == "var"
    assert plan.sparsify.shared_pattern
    metas = {op.meta.tobytes() for op in plan.sparsify.operands}
    assert len(metas) == 1                      # ONE 2:4 pattern for all rows
    # one slot/tap schedule works for every operand
    for s in plan.gather.slots[1:]:
        np.testing.assert_array_equal(s, plan.gather.slots[0])
    # structural kernels are the all-ones band
    for k in plan.decompose.kernels:
        np.testing.assert_array_equal(k, np.ones(2 * spec.radius + 1))
    assert len(plan.decompose.coefficients) == len(plan.decompose.ops)


def test_var_plan_restrictions():
    spec = make_stencil("box", 2, 1, seed=7)
    c = _coeff(spec, (8, 8))
    with pytest.raises(ValueError, match="jnp backends"):
        lower_spec(spec, "pallas_mxu", coefficients=c)
    with pytest.raises(ValueError, match="temporal"):
        lower_spec(spec, "gemm", coefficients=c, temporal_steps=2)
    with pytest.raises(ValueError, match="fuse_rows"):
        lower_spec(spec, "gemm", coefficients=c, fuse_rows=True)


def test_validate_coefficients_shape_and_star_cross():
    spec = make_stencil("star", 2, 1, seed=8)
    with pytest.raises(ValueError, match="shape"):
        validate_coefficients(spec, np.zeros((8, 8, 3)))
    bad = np.ones((8, 8, 3, 3))                 # corners of a star kernel
    with pytest.raises(ValueError, match="cross"):
        validate_coefficients(spec, bad)
    ok = _coeff(spec, (8, 8))
    np.testing.assert_array_equal(validate_coefficients(spec, ok), ok)


# ---------------------------------------------------------------------------
# temporal blocking + errors + describe
# ---------------------------------------------------------------------------

def test_temporal_steps_is_an_ir_attribute():
    plan = lower_spec(make_stencil("star", 2, 1, seed=9), "sptc",
                      temporal_steps=4)
    assert plan.emit.temporal_steps == 4
    assert "k=4" in plan.describe()
    with pytest.raises(ValueError, match="temporal_steps"):
        lower_spec(make_stencil("star", 2, 1, seed=9), "sptc",
                   temporal_steps=0)


def test_lower_spec_rejects_unknown_backend():
    with pytest.raises(ValueError, match="backend"):
        lower_spec(make_stencil("box", 1, 1, seed=0), "cuda")


def test_describe_renders_pipeline():
    spec = make_stencil("star", 2, 1, seed=10)
    d = lower_spec(spec, "sptc").describe()
    assert d.startswith(spec.name)
    for name in ("row-decompose[star-axis x2]", "kernel-matrix[L4]",
                 "strided-swap[2:4", "gather-schedule", "emit[sptc]"):
        assert name in d


def test_validate_catches_structural_breakage():
    plan = lower_spec(make_stencil("box", 1, 1, seed=11), "sptc")
    # out-of-order stages
    bad = ir.LoweredPlan(spec=plan.spec, L=plan.L,
                         stages=tuple(reversed(plan.stages)))
    with pytest.raises(ValueError, match="stage order"):
        bad.validate()
    # missing required stage for a sparse backend
    nosp = ir.LoweredPlan(
        spec=plan.spec, L=plan.L,
        stages=tuple(s for s in plan.stages
                     if not isinstance(s, ir.StridedSwapSparsify)))
    with pytest.raises(ValueError, match="strided-swap"):
        nosp.validate()
    # shared_pattern flag lying about differing metadata: the 2-D star's
    # axis kernels have different zero structure, hence different meta
    star = lower_spec(make_stencil("star", 2, 1, seed=11), "sptc")
    sp = star.sparsify
    assert not sp.shared_pattern
    lying = dataclasses.replace(sp, shared_pattern=True)
    stages = tuple(lying if isinstance(s, ir.StridedSwapSparsify) else s
                   for s in star.stages)
    with pytest.raises(ValueError, match="shared_pattern"):
        ir.LoweredPlan(spec=star.spec, L=star.L, stages=stages).validate()


def test_engine_exposes_plan_ir():
    from repro.core.engine import StencilEngine
    spec = make_stencil("box", 2, 2, seed=12)
    eng = StencilEngine(spec, backend="sptc", fuse_rows=True)
    assert eng.plan_ir.emit.backend == "sptc"
    assert eng.plan_ir.emit.fuse_rows
    assert eng.plan_ir.stage_names() == (
        "row-decompose", "kernel-matrix", "strided-swap",
        "gather-schedule", "emit")


def test_box3d_on_pallas_sptc_lowers_to_planes():
    """A 3-D box on the fused SpTC kernel decomposes by plane columns:
    one op along y per (z, x) offset; other backends, temporal blocking
    and radius 2 keep the row decomposition."""
    spec = make_stencil("box", 3, 1, seed=52)
    plan = lower_spec(spec, "pallas_sptc")
    dec = plan.decompose
    assert dec.mode == "planes" and len(dec.ops) == 9
    assert {op.axis for op in dec.ops} == {1}
    assert sorted(op.lead for op in dec.ops) == [(a, c) for a in range(3)
                                                 for c in range(3)]
    for op in dec.ops:
        a, c = op.lead
        np.testing.assert_array_equal(dec.kernels[op.operand],
                                      spec.weights[a, :, c])
    assert plan.sparsify.shared_pattern
    assert "row-decompose[planes x9]" in plan.describe()
    assert lower_spec(spec, "sptc").decompose.mode == "rows"
    assert lower_spec(spec, "pallas_sptc",
                      temporal_steps=2).decompose.mode == "rows"
    assert lower_spec(make_stencil("box", 3, 2, seed=52),
                      "pallas_sptc").decompose.mode == "rows"
