"""Per-kernel Pallas (interpret=True) vs ref.py oracle sweeps over
shapes & dtypes, per the kernel deliverable contract."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparsify import sparsify_stencil_kernel
from repro.core.stencil import make_stencil
from repro.core.engine import apply_stencil


DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else \
        dict(rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# sptc_spmm fused — window DMA + in-kernel swap/gather + MXU, one program
# ---------------------------------------------------------------------------

def _direct_1d(w, x, n_out):
    return np.stack([np.tensordot(w, x[i:i + len(w)], axes=(0, 0))
                     for i in range(n_out)])


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("r,c", [(1, 64), (1, 200), (2, 128), (3, 384),
                                 (5, 96), (7, 513)])
def test_sptc_fused_general_vs_direct(r, c, dtype, rng):
    from repro.kernels.sptc_spmm.ops import sptc_spmm_fused
    w = rng.normal(size=2 * r + 1)
    sk = sparsify_stencil_kernel(w)
    n_out = 3 * sk.L + 2
    x = jnp.asarray(rng.normal(size=(n_out + 2 * r, c)), dtype)
    got = sptc_spmm_fused(sk.sparse, sk.perm, x, n_out=n_out,
                          L=sk.L, star_fast=False, interpret=True)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               _direct_1d(w, np.asarray(x, np.float32), n_out),
                               **_tol(dtype))


@pytest.mark.parametrize("t", [1, 3, 8])
def test_sptc_fused_vmap_vs_direct(t, rng):
    """``vmap`` over a batch of inputs runs the one-hot kernel on its own
    batch grid axis (``common.fold_vmap``)."""
    import jax
    from repro.kernels.sptc_spmm.ops import sptc_spmm_fused
    w = rng.normal(size=5)                                   # r = 2
    sk = sparsify_stencil_kernel(w)
    n_out = 2 * sk.L + 3
    x = rng.normal(size=(t, n_out + 4, 130)).astype(np.float32)
    call = lambda v: sptc_spmm_fused(sk.sparse, sk.perm, v, n_out=n_out,
                                     L=sk.L, star_fast=False, interpret=True)
    got = jax.vmap(call)(jnp.asarray(x))
    want = np.stack([_direct_1d(w, xb, n_out) for xb in x])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("d", [1, 2, 3])
def test_pallas_sptc_star_ops_are_bands(d):
    """Every row op ``lower_spec`` makes for a star on ``pallas_sptc``, at
    every radius 1-7 and every tuner ``L``, is a contiguous band of its
    taps, so the engine can always run a star on the star kernel."""
    from repro.core.sparsify import contiguous_band_values
    from repro.core.transform import lower_spec
    from repro.tuner.search import l_candidates
    for r in range(1, 8):
        spec = make_stencil("star", d, r, seed=r)
        for L in l_candidates(r):
            plan = lower_spec(spec, backend="pallas_sptc", L=L)
            sp = plan.sparsify
            assert plan.decompose.mode in ("single", "star-axis")
            for op in plan.decompose.ops:
                assert contiguous_band_values(
                    sp.operands[op.operand], sp.perm) is not None, (r, L, op)


@pytest.mark.parametrize("r", [1, 2, 3])
def test_sptc_fused_star_fast_path_vs_direct(r, rng):
    """The metadata-free banded path fires for every banded 1-D kernel
    (the swap∘meta gather is the identity band of the taps)."""
    from repro.core.sparsify import contiguous_band_values
    from repro.kernels.sptc_spmm.ops import sptc_spmm_fused
    w = rng.normal(size=2 * r + 1)
    sk = sparsify_stencil_kernel(w)
    assert contiguous_band_values(sk.sparse, sk.perm) is not None
    n_out = 2 * sk.L + 3
    x = rng.normal(size=(n_out + 2 * r, 130)).astype(np.float32)
    got = sptc_spmm_fused(sk.sparse, sk.perm, jnp.asarray(x), n_out=n_out,
                          L=sk.L, star_fast=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), _direct_1d(w, x, n_out),
                               rtol=2e-5, atol=2e-5)


def test_sptc_fused_bf16_accumulates_f32(rng):
    from repro.kernels.sptc_spmm.ops import sptc_spmm_fused
    w = rng.normal(size=5)                                   # r = 2
    sk = sparsify_stencil_kernel(w)
    n_out = 2 * sk.L
    x = rng.normal(size=(n_out + 4, 128)).astype(np.float32)
    got = sptc_spmm_fused(sk.sparse, sk.perm, jnp.asarray(x), n_out=n_out,
                          L=sk.L, star_fast=True, compute_dtype="bfloat16",
                          interpret=True)
    assert got.dtype == jnp.float32          # output stays in input dtype
    np.testing.assert_allclose(np.asarray(got), _direct_1d(w, x, n_out),
                               rtol=3e-2, atol=3e-2)


def test_sptc_fused_star_rejects_non_band_operand(rng):
    """The star kernel takes only an operand whose swap∘meta gather is the
    identity band of the taps: another one raises, with no fallback."""
    from repro.core.sparsify import encode_24, strided_swap_perm
    from repro.kernels.sptc_spmm.ops import sptc_spmm_fused
    L = 4
    mat = np.zeros((L, 2 * L))
    mat[0, [4, 6]] = 1.0          # row 0 reads input rows 4 and 6: off-band
    x = jnp.asarray(rng.normal(size=(L + 2, 64)), jnp.float32)
    with pytest.raises(ValueError, match="identity band"):
        sptc_spmm_fused(encode_24(mat), strided_swap_perm(L), x, n_out=L,
                        L=L, star_fast=True)


def test_sptc_fused_rejects_non_swap_perm(rng):
    from repro.kernels.sptc_spmm.ops import sptc_spmm_fused
    sk = sparsify_stencil_kernel(rng.normal(size=3))
    x = jnp.asarray(rng.normal(size=(20, 64)), jnp.float32)
    with pytest.raises(ValueError, match="strided-swap"):
        sptc_spmm_fused(sk.sparse, np.arange(2 * sk.L), x, n_out=8, L=sk.L,
                        star_fast=True)


def _pallas_eqns(jaxpr):
    """Every ``pallas_call`` equation in ``jaxpr`` and its nested jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _pallas_eqns(sub)


def _star_call(L, n_out, c, dtype):
    """(input rows the kernel reads after the wrapper's pad, grid, VMEM
    limit, output shape) of the star path's one ``pallas_call`` on an
    (n_out, c) input: traced, not run."""
    import jax
    from repro.kernels.sptc_spmm.kernel import sptc_fused_call
    call = lambda x: sptc_fused_call(
        jnp.zeros((L, L), dtype), jnp.zeros((L, 1), jnp.uint32), x,
        n_out=n_out, L=L, star_fast=True, interpret=True)
    jaxpr = jax.make_jaxpr(call)(jax.ShapeDtypeStruct((n_out, c), dtype))
    (eqn,) = _pallas_eqns(jaxpr.jaxpr)
    limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    return (eqn.invars[0].aval.shape[1], eqn.params["grid_mapping"].grid,
            limit, eqn.outvars[0].aval.shape)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("L", [4, 6, 8])
@pytest.mark.parametrize("n_out,c", [("small", 64), (10240, 10240),
                                     (1000, 2200)])
def test_sptc_fused_geometry(L, dtype, n_out, c):
    from repro.kernels.sptc_spmm.kernel import (_STAR_VMEM_BUDGET,
                                                _fused_geometry,
                                                star_vmem_bytes)
    n_out = 3 * L + 2 if n_out == "small" else n_out
    sub = 32 // jnp.dtype(dtype).itemsize
    m = L * sub // math.gcd(L, sub)
    lane_blocks = lambda bn: -(-c // bn)
    B, rows, bn = _fused_geometry(L, n_out, c, dtype, None, star_fast=False)
    assert B % m == 0 and rows % sub == 0
    assert B + L <= rows <= 128                   # one MXU contraction tile
    tiles = -(-n_out // B)
    assert (tiles - 1) * B < n_out <= tiles * B
    if n_out < 128:                               # clamped to the output
        assert B == -(-n_out // m) * m
    else:                                         # the widest B's steps
        assert tiles == -(-n_out // ((128 - L) // m * m))
    nj = lane_blocks(bn)                          # equal 128-lane multiples
    assert bn % 128 == 0 and bn <= 2048 and nj * bn - c < 128 * nj
    # the star path: steps as large as the VMEM it counts allows
    sB, srows, sbn = _fused_geometry(L, n_out, c, dtype, None, star_fast=True)
    assert sB % m == 0 and srows % sub == 0 and sB + L <= srows
    stiles = -(-n_out // sB)
    assert (stiles - 1) * sB < n_out <= stiles * sB
    nj = lane_blocks(sbn)
    assert sbn % 128 == 0 and sbn <= 2048 and nj * sbn - c < 128 * nj
    need, grid, limit, _ = _star_call(L, n_out, c, dtype)
    assert grid == (1, nj, stiles)
    assert star_vmem_bytes(sB, srows, sbn, L, dtype) <= limit <= _STAR_VMEM_BUDGET
    # no more rows padded than steps of lcm(L, sub) rows each took
    assert need <= (-(-n_out // m) - 1) * m + -(-(m + L) // sub) * sub


def test_sptc_fused_geometry_box_cell():
    """Box-2D49P's row op at 10240²: 120-row steps from a 128-row window,
    2048 lanes: 86 × 5 grid steps where 8-row steps took 1280 × 20."""
    from repro.kernels.sptc_spmm.kernel import _fused_geometry
    assert _fused_geometry(8, 10240, 10240, jnp.float32, None,
                           star_fast=False) == (120, 128, 2048)
    assert _fused_geometry(8, 10240, 10240, jnp.float32, "bfloat16",
                           star_fast=False)[:2] == (112, 128)


@pytest.mark.parametrize("n_out,c,geometry,grid,need", [
    (10240, 10240, (384, 392, 2048), (1, 5, 27), 10248),    # heat-2d.iterate
    (20478, 20478, (384, 392, 2048), (1, 10, 54), 20488),   # 2x2 interior
    (1, 20480, (8, 16, 2048), (1, 10, 1), 8),               # 2x2 rims
    (20480, 1, (5120, 5128, 128), (1, 1, 4), 20488),
])
def test_sptc_fused_geometry_heat_cells(n_out, c, geometry, grid, need):
    """Heat-2D's star row ops (L = 4, f32): at 10240² 384-row steps over
    2048 lanes, 27 × 5 grid steps where 8-row, 512-lane steps took
    1280 × 20, with the same 10248 input rows; the 2×2's interior and rim
    slabs likewise, a short output in one step and a one-lane extent in
    tall steps over 128 lanes.  The kernel writes the output at its own
    size, so nothing is cropped after the call."""
    from repro.kernels.sptc_spmm.kernel import _fused_geometry
    assert _fused_geometry(4, n_out, c, jnp.float32, None,
                           star_fast=True) == geometry
    got_need, got_grid, _, out = _star_call(4, n_out, c, jnp.float32)
    assert (got_need, got_grid, out) == (need, grid, (1, n_out, c))


@pytest.mark.parametrize("star_fast,n_out", [(False, 397), (True, 1700)])
@pytest.mark.parametrize("r,batch", [(1, None), (2, None), (3, None),
                                     (2, 2)])
def test_sptc_fused_onehot_row_blocks_vs_direct(r, batch, star_fast, n_out,
                                                rng):
    """Several row blocks with a ragged tail, and a lane extent that is no
    multiple of the lane block, on either path; the star path's last step
    reads a short window.  ``batch`` runs through ``vmap`` (the kernel's
    batch grid axis)."""
    import jax
    from repro.kernels.sptc_spmm.kernel import _fused_geometry
    from repro.kernels.sptc_spmm.ops import sptc_spmm_fused
    w = rng.normal(size=2 * r + 1)
    sk = sparsify_stencil_kernel(w)
    c = 2200
    B, rows, bn = _fused_geometry(sk.L, n_out, c, jnp.float32, None,
                                  star_fast)
    tiles = -(-n_out // B)
    assert tiles >= 3 and n_out % B and c % bn and c > bn
    if star_fast:
        assert _star_call(sk.L, n_out, c, jnp.float32)[0] < (
            (tiles - 1) * B + rows)
    lead = () if batch is None else (batch,)
    x = rng.normal(size=lead + (n_out + 2 * r, c)).astype(np.float32)
    call = lambda v: sptc_spmm_fused(sk.sparse, sk.perm, v, n_out=n_out,
                                     L=sk.L, star_fast=star_fast,
                                     interpret=True)
    got = np.asarray(call(jnp.asarray(x)) if batch is None
                     else jax.vmap(call)(jnp.asarray(x)))
    want = np.stack([_direct_1d(w, xb, n_out) for xb in x.reshape(
        (-1,) + x.shape[-2:])]).reshape(lead + (n_out, c))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# interpret-mode defaults (all four kernel packages' *_call entry points)
# ---------------------------------------------------------------------------

def test_default_interpret_env_override(monkeypatch):
    from repro.kernels import common
    monkeypatch.delenv(common.INTERPRET_ENV_VAR, raising=False)
    assert common.default_interpret() is True          # CPU container
    monkeypatch.setenv(common.INTERPRET_ENV_VAR, "0")
    assert common.default_interpret() is False
    monkeypatch.setenv(common.INTERPRET_ENV_VAR, "1")
    assert common.default_interpret() is True


def test_all_call_entry_points_default_interpret_to_backend():
    """interpret must default to None (resolved off the device at call
    time), never a hardcoded True that silently slow-paths a real TPU."""
    import inspect
    from repro.kernels.conv1d.kernel import conv1d_causal_call
    from repro.kernels.sptc_spmm.kernel import (sptc_fused_call,
                                                sptc_planes_call)
    from repro.kernels.stencil_direct.kernel import stencil2d_call
    from repro.kernels.stencil_gemm.kernel import windows_gemm_call
    for fn in (sptc_fused_call, sptc_planes_call, windows_gemm_call,
               stencil2d_call, conv1d_causal_call):
        sig = inspect.signature(fn)
        assert sig.parameters["interpret"].default is None, fn.__name__


def test_sptc_fused_call_interpret_none_matches_explicit(rng):
    from repro.kernels.sptc_spmm.kernel import sptc_fused_call
    sk = sparsify_stencil_kernel(rng.normal(size=3))
    n_out = 2 * sk.L
    x = jnp.asarray(rng.normal(size=(n_out + 2, 64)), jnp.float32)
    vals = jnp.asarray(sk.values, jnp.float32)
    meta_bits = jnp.asarray(sk.sparse.meta_bits())
    call = lambda **kw: sptc_fused_call(vals, meta_bits, x, n_out=n_out,
                                        L=sk.L, star_fast=False, **kw)
    got = call()                                       # None -> CPU -> True
    want = call(interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# dispatch — pallas_direct 3-D builder
# ---------------------------------------------------------------------------

def test_pallas_direct_3d_zero_kernel_returns_zeros(rng):
    """Regression: fn3d returned None when every leading-axis slab was
    all-zero (every slab skipped, accumulator never initialized)."""
    from repro.core.stencil import StencilSpec
    from repro.kernels.dispatch import build
    spec = StencilSpec(shape="box", ndim=3, radius=1,
                       weights=np.zeros((3, 3, 3)))
    fn = build(spec, "pallas_direct", 4)
    x = jnp.asarray(rng.normal(size=(8, 10, 12)), jnp.float32)
    y = fn(x)
    assert y is not None
    assert y.shape == (6, 8, 10) and y.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(y), np.zeros((6, 8, 10)))


# ---------------------------------------------------------------------------
# stencil_gemm — dense windows GEMM (Tensor-Core baseline analogue)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("l,t,c", [(4, 1, 64), (6, 4, 128), (8, 3, 200),
                                   (16, 2, 512)])
def test_windows_gemm_vs_ref(l, t, c, dtype, rng):
    from repro.kernels.stencil_gemm.ops import windows_gemm
    from repro.kernels.stencil_gemm.ref import windows_gemm_ref
    km = jnp.asarray(rng.normal(size=(l, 2 * l)), dtype)
    win = jnp.asarray(rng.normal(size=(t, 2 * l, c)), dtype)
    got = windows_gemm(km, win, interpret=True)
    want = windows_gemm_ref(km, win)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# stencil_direct — tiled VPU shift-FMA kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,r", [("box", 1), ("box", 2), ("box", 3),
                                     ("star", 2)])
@pytest.mark.parametrize("dims", [(16, 16), (40, 130), (128, 256), (37, 91)])
def test_stencil_direct_2d_vs_ref(shape, r, dims, rng):
    from repro.kernels.stencil_direct.ops import stencil2d
    from repro.kernels.stencil_direct.ref import stencil2d_ref
    spec = make_stencil(shape, 2, r, seed=13)
    x = jnp.asarray(rng.normal(size=(dims[0] + 2 * r, dims[1] + 2 * r)),
                    jnp.float32)
    got = stencil2d(spec.weights, x, interpret=True)
    want = stencil2d_ref(spec.weights, x)
    assert got.shape == dims
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n,r", [(100, 1), (1000, 2), (4096, 3)])
def test_stencil_direct_1d_vs_ref(n, r, rng):
    from repro.kernels.stencil_direct.ops import stencil1d
    spec = make_stencil("box", 1, r, seed=3)
    x = rng.normal(size=(n + 2 * r,)).astype(np.float32)
    got = stencil1d(spec.weights, jnp.asarray(x), interpret=True)
    want = np.correlate(x, spec.weights[::-1], mode="valid")[::-1][::-1]
    # np.correlate(x, w) flips nothing for symmetric check; compute directly:
    want = np.array([np.dot(spec.weights, x[i:i + 2 * r + 1])
                     for i in range(n)])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_pallas_direct_backend_full_stencils(rng):
    """Whole-engine pallas_direct backend vs direct for 1/2/3-D."""
    for shape, ndim, r in [("box", 2, 1), ("star", 2, 2), ("box", 3, 1)]:
        spec = make_stencil(shape, ndim, r, seed=1)
        dims = {2: (24, 40), 3: (9, 12, 20)}[ndim]
        x = jnp.asarray(
            rng.normal(size=tuple(s + 2 * r for s in dims)), jnp.float32)
        want = apply_stencil(spec, x, backend="direct")
        got = apply_stencil(spec, x, backend="pallas_direct")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_pallas_backends_via_engine(rng):
    """pallas_mxu / pallas_sptc engine paths vs direct on a 2-D box."""
    spec = make_stencil("box", 2, 2, seed=9)
    x = jnp.asarray(rng.normal(size=(36, 52)), jnp.float32)
    want = apply_stencil(spec, x, backend="direct")
    for backend in ("pallas_mxu", "pallas_sptc"):
        got = apply_stencil(spec, x, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=backend)


# ---------------------------------------------------------------------------
# conv1d — depthwise causal conv (the technique's LM integration point)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("b,t,d,k", [(1, 16, 8, 4), (2, 100, 64, 4),
                                     (3, 257, 128, 4), (1, 32, 200, 2)])
def test_conv1d_vs_ref(b, t, d, k, dtype, rng):
    from repro.kernels.conv1d.ops import conv1d_causal
    from repro.kernels.conv1d.ref import conv1d_causal_ref
    x = jnp.asarray(rng.normal(size=(b, t, d)), dtype)
    w = jnp.asarray(rng.normal(size=(k, d)), dtype)
    got = conv1d_causal(x, w, interpret=True)
    want = conv1d_causal_ref(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_conv1d_causality(rng):
    """Output at t must not depend on inputs after t."""
    from repro.kernels.conv1d.ref import conv1d_causal_ref
    x = jnp.asarray(rng.normal(size=(1, 20, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    y1 = conv1d_causal_ref(x, w)
    x2 = x.at[:, 10:, :].set(999.0)
    y2 = conv1d_causal_ref(x2, w)
    np.testing.assert_array_equal(np.asarray(y1[:, :10]),
                                  np.asarray(y2[:, :10]))


# ---------------------------------------------------------------------------
# sptc_onehot3d — the planes kernel's row tiles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r", [1, 2, 3])
@pytest.mark.parametrize("ny", [5, 9, 26, 128, 130, 133, 300, 484, 1026, 1032])
def test_planes_geometry(r, ny):
    """Tiles start on sublane tiles, fit the plane's block, and give every
    interior row the whole window its taps read; B + 2r fits one MXU tile."""
    from repro.kernels.common import MXU, round_up
    from repro.kernels.sptc_spmm.kernel import _planes_geometry
    if ny <= 2 * r:
        return
    B, rows, runs, shifts = _planes_geometry(r, ny)
    by = round_up(ny, 8)
    assert B % 8 == 0 and rows <= MXU and (B + 2 * r <= MXU or B == by)
    covered = set()
    for o0, s0, j, n in runs:
        assert n == 1 or shifts[j] == 0
        for k in range(n):
            o, s = o0 + k * B, s0 + k * B
            assert o % 8 == 0 and o + B <= by and 0 <= s and s + rows <= ny
            assert o - s - r == shifts[j]
            for p in range(max(o, r), min(o + B, ny - r)):
                assert s <= p - r and p + r < s + rows
                covered.add(p)
    assert covered == set(range(r, ny - r))


def test_planes_geometry_box_cell():
    """Box-3D27P's 1026-row planes: 120-row tiles from 128-row windows, the
    seven between the first and the last in one run."""
    from repro.kernels.sptc_spmm.kernel import _planes_geometry
    B, rows, runs, shifts = _planes_geometry(1, 1026)
    assert (B, rows) == (120, 128)
    assert runs == ((0, 0, 0, 1), (120, 119, 1, 7), (912, 898, 2, 1))
    assert shifts == (-1, 0, 13)
