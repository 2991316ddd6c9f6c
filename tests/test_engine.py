"""StencilEngine backend-equivalence tests: every backend must compute the
same stencil as the direct shifted-FMA oracle, across the paper's suite."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import StencilEngine, apply_stencil
from repro.core.stencil import make_stencil, paper_suite, star_mask
from repro.core.sptc import sptc_matmul, swap_rows
from repro.core.sparsify import sparsify_stencil_kernel
from repro.core.transform import kernel_matrix


def _ref(spec, x):
    """numpy oracle: dense correlation with the stencil weights."""
    r, d = spec.radius, spec.ndim
    w = spec.weights
    out_shape = tuple(s - 2 * r for s in x.shape)
    out = np.zeros(out_shape)
    for off in np.ndindex(*w.shape):
        if w[off] == 0:
            continue
        sl = tuple(slice(o, o + n) for o, n in zip(off, out_shape))
        out += w[off] * x[sl]
    return out


BACKENDS = ["gemm", "sptc"]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("shape,ndim,r", [
    ("box", 1, 1), ("box", 1, 2), ("star", 2, 1), ("star", 2, 3),
    ("box", 2, 1), ("box", 2, 2), ("box", 2, 3), ("box", 3, 1),
    ("star", 3, 2),
])
def test_backends_match_direct(backend, shape, ndim, r, rng):
    spec = make_stencil(shape, ndim, r, seed=11)
    dims = {1: (203,), 2: (37, 41), 3: (13, 15, 17)}[ndim]
    x = rng.normal(size=tuple(s + 2 * r for s in dims)).astype(np.float32)
    want = _ref(spec, x)
    got = apply_stencil(spec, jnp.asarray(x), backend=backend)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape,ndim,r", [("box", 2, 2), ("star", 2, 2)])
def test_direct_backend_matches_numpy(shape, ndim, r, rng):
    spec = make_stencil(shape, ndim, r, seed=7)
    x = rng.normal(size=(40 + 2 * r, 52 + 2 * r)).astype(np.float32)
    got = apply_stencil(spec, jnp.asarray(x), backend="direct")
    np.testing.assert_allclose(np.asarray(got), _ref(spec, x),
                               rtol=2e-5, atol=2e-5)


def test_engine_iterate_stable(rng):
    """Iterated smoothing stencil stays bounded (weights sum to 1)."""
    spec = make_stencil("box", 2, 1, seed=0)
    eng = StencilEngine(spec, backend="direct")
    x = jnp.asarray(rng.uniform(0, 1, size=(34, 34)).astype(np.float32))
    y = eng.iterate(x, steps=10)
    assert y.shape == x.shape
    assert np.all(np.isfinite(np.asarray(y)))
    assert float(jnp.max(jnp.abs(y))) <= 1.0 + 1e-4


def test_nonsquare_kernel_matrix_beats_tcstencil():
    """Paper §3.2.1: rectangular K (L x 2r+L) has no blank rows — every row
    holds a full kernel copy (TCStencil's square L x L wastes 2r rows)."""
    for r in (1, 2, 3):
        K = kernel_matrix(np.ones(2 * r + 1), pad_width=False)
        assert np.all((K != 0).sum(axis=1) == 2 * r + 1)


def test_sptc_matmul_equals_dense(rng):
    """Simulated mma.sp == dense matmul with the permuted banded matrix."""
    for r in (1, 2, 3, 5):
        w = rng.normal(size=2 * r + 1)
        sk = sparsify_stencil_kernel(w)
        K = kernel_matrix(w, L=sk.L, pad_width=True)
        x = rng.normal(size=(2 * sk.L, 19)).astype(np.float32)
        got = sptc_matmul(jnp.asarray(sk.values, jnp.float32),
                          jnp.asarray(sk.meta), jnp.asarray(x[sk.perm]))
        np.testing.assert_allclose(np.asarray(got), K @ x, rtol=2e-5,
                                   atol=1e-5)


def test_swap_rows_reference():
    x = np.arange(8.0)[:, None] * np.ones((1, 3))
    perm = np.array([0, 5, 2, 7, 4, 1, 6, 3])
    np.testing.assert_array_equal(np.asarray(swap_rows(jnp.asarray(x), perm)),
                                  x[perm])


@pytest.mark.parametrize("backend", ["direct", "gemm", "sptc"])
def test_bf16_inputs(backend, rng):
    spec = make_stencil("box", 2, 1, seed=2)
    x = rng.normal(size=(20, 24)).astype(np.float32)
    xb = jnp.asarray(x, jnp.bfloat16)
    got = apply_stencil(spec, xb, backend=backend)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               _ref(spec, x)[:, :], rtol=5e-2, atol=5e-2)


def test_paper_suite_all_runs():
    for spec in paper_suite():
        dims = {1: (130,), 2: (18, 22), 3: (9, 10, 11)}[spec.ndim]
        x = jnp.ones(tuple(s + 2 * spec.radius for s in dims))
        y = apply_stencil(spec, x, backend="sptc")
        assert y.shape == dims
        # smoothing kernel of all-ones input -> all ones out
        np.testing.assert_allclose(np.asarray(y), 1.0, rtol=1e-5)


@pytest.mark.parametrize("backend", ["gemm", "sptc"])
@pytest.mark.parametrize("shape,r", [("box", 1), ("box", 2), ("box", 3)])
def test_fused_rows_matches_unfused(backend, shape, r, rng):
    """§Perf D fused execution: one stacked GEMM == per-row application."""
    spec = make_stencil(shape, 2, r, seed=4)
    x = jnp.asarray(rng.normal(size=(41 + 2 * r, 57 + 2 * r)), jnp.float32)
    want = StencilEngine(spec, backend=backend)(x)
    got = StencilEngine(spec, backend=backend, fuse_rows=True)(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# variable coefficients: per-point weights through ONE shared 2:4 pattern
# ---------------------------------------------------------------------------

VAR_SWEEP = [("box", 1, 1), ("box", 1, 2), ("box", 1, 3),
             ("star", 2, 1), ("star", 2, 2), ("star", 2, 3),
             ("box", 2, 1), ("box", 2, 2), ("box", 2, 3)]


def _rand_coefficients(spec, out_shape, rng):
    """Random per-output-point kernel field, star cross honored."""
    taps = 2 * spec.radius + 1
    c = rng.normal(size=out_shape + (taps,) * spec.ndim)
    if spec.shape == "star":
        c[..., ~star_mask(spec.ndim, spec.radius)] = 0.0
    return c


def _var_ref(spec, x, c):
    """numpy oracle: out[i] = sum_off c[i, off] * x[i + off]."""
    r, d = spec.radius, spec.ndim
    out_shape = tuple(s - 2 * r for s in x.shape)
    out = np.zeros(out_shape)
    for off in np.ndindex(*(2 * r + 1,) * d):
        sl = tuple(slice(o, o + n) for o, n in zip(off, out_shape))
        out += c[(slice(None),) * d + off] * x[sl]
    return out


@pytest.mark.parametrize("shape,ndim,r", VAR_SWEEP)
def test_variable_coefficients_match_oracle(shape, ndim, r, rng):
    """Radius sweep: every var-coeff backend == the per-point numpy oracle."""
    spec = make_stencil(shape, ndim, r, seed=13)
    dims = {1: (53,), 2: (13, 17)}[ndim]
    c = _rand_coefficients(spec, dims, rng)
    x = rng.normal(size=tuple(s + 2 * r for s in dims)).astype(np.float32)
    want = _var_ref(spec, x, c)
    for backend in ("direct", "gemm", "sptc"):
        eng = StencilEngine(spec, backend=backend, coefficients=c)
        got = np.asarray(eng(jnp.asarray(x)))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                   err_msg=f"{backend} {spec.name}")


def test_variable_coefficients_reduce_to_constant(rng):
    """A field replicating the spec's weights == the constant-kernel path."""
    spec = make_stencil("star", 2, 2, seed=5)
    dims = (12, 15)
    c = np.broadcast_to(spec.weights, dims + spec.weights.shape).copy()
    x = jnp.asarray(rng.normal(size=(16, 19)), jnp.float32)
    want = apply_stencil(spec, x, backend="direct")
    got = StencilEngine(spec, backend="sptc", coefficients=c)(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_variable_coefficient_engine_is_fixed_shape(rng):
    spec = make_stencil("box", 2, 1, seed=3)
    c = _rand_coefficients(spec, (10, 12), rng)
    eng = StencilEngine(spec, backend="sptc", coefficients=c)
    assert eng.plan_ir.sparsify.shared_pattern
    eng(jnp.zeros((12, 14)))                     # the field's shape: fine
    with pytest.raises(ValueError, match="fixed-shape"):
        eng(jnp.zeros((13, 14)))


# ---------------------------------------------------------------------------
# temporal blocking: k fused steps in one compiled program
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("shape,ndim,r", VAR_SWEEP)
def test_temporal_block_matches_repeated_oracle(shape, ndim, r, k, rng):
    """A k-step engine on a k·r-halo input == k repeated oracle sweeps."""
    spec = make_stencil(shape, ndim, r, seed=17)
    dims = {1: (45,), 2: (11, 13)}[ndim]
    x = rng.normal(size=tuple(s + 2 * k * r for s in dims)).astype(np.float32)
    want = x
    for _ in range(k):
        want = _ref(spec, want)
    for backend in ("direct", "gemm", "sptc"):
        eng = StencilEngine(spec, backend=backend, temporal_steps=k)
        got = np.asarray(eng(jnp.asarray(x)))
        assert got.shape == dims
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                   err_msg=f"{backend} {spec.name} k={k}")


def test_temporal_iterate_matches_blockwise_reference(rng):
    """iterate() with temporal_steps=k: k raw applications per re-pad block."""
    spec = make_stencil("star", 2, 1, seed=0)
    k, steps = 2, 4
    x = rng.uniform(0, 1, size=(20, 22)).astype(np.float32)
    eng = StencilEngine(spec, backend="gemm", temporal_steps=k)
    got = np.asarray(eng.iterate(jnp.asarray(x), steps=steps))
    y = x
    for _ in range(steps // k):
        t = y
        for _ in range(k):
            t = _ref(spec, t)
        y = np.pad(t, k * spec.radius)
    np.testing.assert_allclose(got, y, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="multiple"):
        eng.iterate(jnp.asarray(x), steps=3)


# ---------------------------------------------------------------------------
# 3-D box on pallas_sptc: the "planes" lowering, one kernel a step
# ---------------------------------------------------------------------------

def _loop3d(w, x):
    """NumPy triple loop over the taps of a 3-D stencil (float64)."""
    r = (w.shape[0] - 1) // 2
    n = tuple(s - 2 * r for s in x.shape)
    out = np.zeros(n)
    for a in range(2 * r + 1):
        for b in range(2 * r + 1):
            for c in range(2 * r + 1):
                out += w[a, b, c] * x[a:a + n[0], b:b + n[1], c:c + n[2]]
    return out


@pytest.mark.parametrize("dims,r", [
    ((5, 7, 9), 1),            # one row tile, one lane tile
    ((7, 131, 140), 1),        # two row tiles, two 128-lane tiles
    ((3, 484, 131), 1),        # first tile, a looped run of tiles, last tile
    ((4, 250, 262), 1),        # three row tiles, three lane tiles
    ((5, 25, 30), 2),          # radius 2 keeps the rows decomposition
], ids=["tiny", "two-tiles", "tile-run", "three-tiles", "r2"])
def test_planes_box3d_matches_direct_and_numpy(dims, r, rng):
    """Box-3D on pallas_sptc (interpret mode) equals the direct backend and
    a NumPy loop, on sides that are not multiples of 8 or 128."""
    spec = make_stencil("box", 3, r, seed=52)
    eng = StencilEngine(spec, backend="pallas_sptc")
    assert eng.plan_ir.decompose.mode == ("planes" if r == 1 else "rows")
    x = rng.normal(size=tuple(s + 2 * r for s in dims)).astype(np.float32)
    got = np.asarray(eng(jnp.asarray(x)))
    assert got.shape == dims
    want = np.asarray(StencilEngine(spec, backend="direct")(jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, _loop3d(spec.weights, x.astype(np.float64)),
                               rtol=2e-5, atol=2e-5)


def test_planes_iterate_keeps_a_zero_halo(rng):
    """iterate on the planes path: several steps equal direct with a zero
    re-pad after each, the first step reads the halo it was given, and the
    halo comes out zero."""
    spec = make_stencil("box", 3, 1, seed=52)
    x = jnp.asarray(rng.normal(size=(9, 132, 135)), jnp.float32)
    got = StencilEngine(spec, backend="pallas_sptc").iterate(x, 5)
    direct = StencilEngine(spec, backend="direct")
    want = x
    for _ in range(5):
        want = jnp.pad(direct(want), 1)
    assert got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    halo = np.asarray(got).copy()
    halo[1:-1, 1:-1, 1:-1] = 0.0
    assert not halo.any()


def test_planes_too_wide_for_vmem_run_the_row_ops(rng, monkeypatch):
    """Planes the kernel's VMEM cannot hold run the same row ops one by one
    along ``y``: the step and ``iterate`` still equal direct and a NumPy
    loop, the halo stays zero, and no ``sptc_onehot3d`` call is traced.
    The kernel itself refuses such planes."""
    import jax
    from repro.kernels.sptc_spmm import kernel
    from repro.kernels.sptc_spmm.ops import sptc_planes
    spec = make_stencil("box", 3, 1, seed=52)
    x = jnp.asarray(rng.normal(size=(7, 133, 141)), jnp.float32)
    # the cap one byte under what these planes take
    monkeypatch.setattr(kernel, "_PLANES_VMEM_CAP",
                        kernel.planes_vmem_bytes(x.shape, x.dtype, 1, 9) - 1)
    eng = StencilEngine(spec, backend="pallas_sptc")
    assert "sptc_onehot3d" not in str(jax.make_jaxpr(eng._halo_step)(x))
    direct = StencilEngine(spec, backend="direct")
    np.testing.assert_allclose(np.asarray(eng(x)), np.asarray(direct(x)),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(eng(x)), _loop3d(spec.weights, np.asarray(x, np.float64)),
        rtol=2e-5, atol=2e-5)
    got = np.asarray(eng.iterate(x, 3))
    want = x
    for _ in range(3):
        want = jnp.pad(direct(want), 1)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-5)
    halo = got.copy()
    halo[1:-1, 1:-1, 1:-1] = 0.0
    assert not halo.any()
    sp = eng.plan_ir.sparsify
    with pytest.raises(ValueError, match="too large for the planes kernel"):
        sptc_planes(sp.operands, sp.perm, x,
                    leads=[op.lead for op in eng.plan_ir.decompose.ops],
                    L=eng.L, r=1)


def test_planes_fit_the_cell_and_not_twice_its_width():
    """The cell's 1026^2 planes fit the kernel's VMEM; 2050^2 planes, and
    very wide planes of few rows, do not."""
    from repro.kernels.sptc_spmm.kernel import planes_fit
    assert planes_fit((1026, 1026, 1026), jnp.float32, 1, 9)
    assert not planes_fit((18, 2050, 2050), jnp.float32, 1, 9)
    assert not planes_fit((18, 130, 16384), jnp.float32, 1, 9)


def test_planes_vmap_matches_direct(rng):
    """vmap over the planes engine runs one kernel call per grid."""
    import jax
    spec = make_stencil("box", 3, 1, seed=52)
    xs = jnp.asarray(rng.normal(size=(2, 6, 9, 12)), jnp.float32)
    got = jax.jit(jax.vmap(StencilEngine(spec, backend="pallas_sptc")._fn))(xs)
    direct = StencilEngine(spec, backend="direct")
    for i in range(xs.shape[0]):
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(direct(xs[i])),
                                   rtol=2e-5, atol=2e-5)
