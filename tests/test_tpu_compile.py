"""Compile the main path's kernels for a described TPU v5e, at paper size.

Nothing here runs on a chip: the TPU compiler installed with JAX compiles
for a ``v5e:2x2`` topology that is described, not attached, and refuses
what Mosaic or the chip's memory would refuse (unaligned DMA slices, too
much scoped VMEM, programs larger than HBM).  Interpret mode cannot show
any of that, so these compiles guard every Pallas kernel the engine, the
serving path (``vmap``) and the 2x2 halo-exchange engine use.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.engine import StencilEngine
from repro.core.stencil import make_stencil
from repro.distributed.halo import ShardedStencilEngine, grid_mesh
from repro.kernels.common import INTERPRET_ENV_VAR
from repro.vet.lowering import collective_counts

SIDE = 10240            # the paper's 2-D grid (benchmarks/fig9_throughput.py)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_compile(monkeypatch):
    """Compiled Mosaic (default_interpret() sees the CPU here), no
    persistent cache (an entry compiled for a described chip cannot be
    read back without one) and no TPU runtime log files."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv(INTERPRET_ENV_VAR, "0")
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _grid(spec, sharding, lead=()):
    return jax.ShapeDtypeStruct(lead + (SIDE + 2 * spec.radius,) * 2,
                                jnp.float32, sharding=sharding)


@pytest.mark.parametrize("shape,radius,backend", [
    ("star", 1, "pallas_sptc"),
    ("box", 1, "pallas_sptc"),
    ("star", 3, "pallas_sptc"),
    ("box", 1, "pallas_direct"),
    ("box", 1, "pallas_mxu"),
])
def test_engine_step_compiles_for_v5e(shape, radius, backend, one_chip,
                                      tpu_compile):
    spec = make_stencil(shape, 2, radius, seed=0)
    eng = StencilEngine(spec, backend=backend)
    text = eng._fn.lower(_grid(spec, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backend", ["pallas_sptc", "pallas_direct"])
def test_batched_step_compiles_for_v5e(backend, one_chip, tpu_compile):
    """The serving path runs jit(vmap(engine)); the DMA kernels must batch
    on their own grid axis (Pallas' rule cannot batch an HBM input)."""
    spec = make_stencil("box", 2, 2, seed=0)
    eng = StencilEngine(spec, backend=backend)
    xs = jax.ShapeDtypeStruct((8, 2048, 2048), jnp.float32,
                              sharding=one_chip)
    text = jax.jit(jax.vmap(eng._fn)).lower(xs).compile().as_text()
    assert "tpu_custom_call" in text


def test_sharded_iterate_compiles_for_v5e_2x2(topo, tpu_compile):
    mesh = grid_mesh((2, 2), devices=topo.devices)
    spec = make_stencil("box", 2, 1, seed=0)
    eng = ShardedStencilEngine(spec, mesh, backend="pallas_sptc")
    u = jax.ShapeDtypeStruct((2 * SIDE, 2 * SIDE), jnp.float32,
                             sharding=NamedSharding(mesh, P("sp0", "sp1")))
    text = eng._run.lower(u, 4).compile().as_text()
    assert "tpu_custom_call" in text
    counts = collective_counts(text)
    assert counts["collective-permute"] > 0 and counts["gather-like"] == 0
