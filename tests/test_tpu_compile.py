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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.engine import StencilEngine
from repro.core.stencil import make_stencil, paper_suite
from repro.distributed.halo import ShardedStencilEngine, grid_mesh
from repro.kernels.common import INTERPRET_ENV_VAR
from repro.vet.lowering import collective_counts

SIDE = 10240            # the paper's 2-D grid (bench/configs/*.json)


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


# -- the benchmark's timed programs, stage by stage ---------------------------

#: what runs once per step or per call without doing work on the grid
_NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "while", "conditional", "call"}


def _cell_program(cell, topo):
    """A cell's timed program compiled as its entry builds it
    (``bench/entries/iterate*.py``): the iterate under one jit, state donated."""
    from bench import harness
    bm = harness.load_benchmark()
    wl = harness.load_workload_file(cell)
    cfg = harness.load_config(bm, harness.find_workload(bm, cell)["config"])
    spec = next(s for s in paper_suite() if s.name == cfg["spec"])
    n, spc = int(wl["grid"][0]), int(wl["steps_per_call"])
    if "mesh" in wl:
        mesh = grid_mesh(tuple(wl["mesh"]), devices=topo.devices)
        eng = ShardedStencilEngine(spec, mesh, backend=wl["backend"])
        u = jax.ShapeDtypeStruct((n, n), jnp.float32,
                                 sharding=NamedSharding(mesh, P(*mesh.axis_names)))
    else:
        eng = StencilEngine(spec, backend=wl["backend"])
        u = jax.ShapeDtypeStruct((n + 2 * spec.radius,) * 2, jnp.float32,
                                 sharding=SingleDeviceSharding(topo.devices[0]))
    return jax.jit(lambda v: eng.iterate(v, spc), donate_argnums=0
                   ).lower(u).compile().as_text()


@pytest.mark.parametrize("cell,kernel", [
    ("box-2d49p.iterate", "sptc_onehot"),
    ("heat-2d.iterate", "sptc_star"),
    ("heat-2d.iterate-2x2", "sptc_star"),
])
def test_cell_program_is_covered_by_stages(cell, kernel, topo, tpu_compile):
    """Every instruction of the timed program that works on the grid is a
    kernel, a collective or counts under a stage the program declares
    (``bench/scopes.py``), and the fused kernel carries its path's name."""
    from bench import scopes, trace
    text = _cell_program(cell, topo)
    kernels, collectives = trace.classify_hlo([text])
    assert kernels and {k.rsplit(".", 1)[0] for k in kernels} == {kernel}
    instrs = scopes.parse([text])
    where = scopes.attribute([text])
    entry = re.search(r"^ENTRY %(\S+)", text, re.M).group(1)
    run = {entry} | set(re.findall(r"body=%([^\s,}]+)", text))
    scalar = set(re.findall(r"^\s*(?:ROOT\s+)?%(\S+) = \w+\[\]", text, re.M))
    work = [i for i in instrs.values()
            if i.comp in run and i.opcode not in _NO_WORK and i.name not in scalar]
    stray = [(i.name, i.opcode, i.op_name) for i in work
             if i.name not in kernels and i.name not in collectives
             and where[i.name] == scopes.UNSCOPED]
    assert len(work) > len(kernels) and not stray, stray
    assert {where[c] for c in collectives} <= {"halo.exchange"}
