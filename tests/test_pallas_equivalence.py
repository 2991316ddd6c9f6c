"""CI equivalence sweep: every Pallas backend vs the jnp oracles.

Runs entirely in interpret mode (the CI container has no TPU), over the
registry cross-product radius x dimensionality x shape the paper
benchmarks (§4.1), so a lowering regression in any Pallas backend —
including the fused SpTC v2 kernel behind ``pallas_sptc`` — fails tier-1
before it can reach hardware.  Grids are kept just above one L-tile to
stay inside the tier-1 time budget.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import apply_stencil
from repro.core.stencil import make_stencil
from repro.kernels.dispatch import PALLAS_BACKENDS

RADII = (1, 2, 3)
#: (shape, ndim) registry; 1-D star degenerates to the 1-D box pattern but
#: exercises the star-axis plan mode (and so the fused kernel's fast path).
POINTS = (("box", 1), ("star", 1), ("box", 2), ("star", 2))


def _grid(ndim, radius):
    n = 26 + 2 * radius            # a couple of rows past one L-tile
    return (n,) if ndim == 1 else (n, n + 6)


@pytest.mark.parametrize("radius", RADII)
@pytest.mark.parametrize("shape,ndim", POINTS)
def test_pallas_backends_match_direct(shape, ndim, radius, rng):
    spec = make_stencil(shape, ndim, radius, seed=10 * ndim + radius)
    x = jnp.asarray(rng.normal(size=_grid(ndim, radius)), jnp.float32)
    want = np.asarray(apply_stencil(spec, x, backend="direct"))
    for backend in PALLAS_BACKENDS:
        got = apply_stencil(spec, x, backend=backend)
        assert got.shape == want.shape, backend
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=3e-5, atol=3e-5,
            err_msg=f"{backend} diverged on {shape}/{ndim}d r={radius}")


@pytest.mark.parametrize("backend", PALLAS_BACKENDS)
def test_pallas_backends_vmap_match_direct(backend, rng):
    """vmap over an engine (the serving path's jit(vmap)) runs the DMA
    kernels on their own batch grid axis; every job matches direct."""
    import jax
    from repro.core.engine import StencilEngine
    spec = make_stencil("box", 2, 1, seed=21)
    xs = jnp.asarray(rng.normal(size=(3, 20, 30)), jnp.float32)
    got = jax.jit(jax.vmap(StencilEngine(spec, backend=backend)._fn))(xs)
    direct = StencilEngine(spec, backend="direct")
    for i in range(xs.shape[0]):
        np.testing.assert_allclose(np.asarray(got[i]),
                                   np.asarray(direct(xs[i])),
                                   rtol=3e-5, atol=3e-5, err_msg=backend)
