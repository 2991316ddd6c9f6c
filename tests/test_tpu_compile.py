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
import math
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


def _cell_trace(cell, topo):
    """A cell's timed program traced as its entry builds it
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
        u = jax.ShapeDtypeStruct((n + 2 * spec.radius,) * len(wl["grid"]),
                                 jnp.float32,
                                 sharding=SingleDeviceSharding(topo.devices[0]))
    return jax.jit(lambda v: eng.iterate(v, spc), donate_argnums=0).trace(u)


def _cell_program(cell, topo):
    """A cell's timed program, compiled."""
    return _cell_trace(cell, topo).lower().compile().as_text()


def _pallas_eqns(jaxpr):
    """Every ``pallas_call`` equation in ``jaxpr`` and its nested jaxprs
    (jit, scan, custom_vmap)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    yield from _pallas_eqns(sub)


def _pallas_grids(jaxpr):
    """(kernel name, grid) of every ``pallas_call`` in ``jaxpr``."""
    for eqn in _pallas_eqns(jaxpr):
        yield eqn.params["name"], eqn.params["grid_mapping"].grid


@pytest.mark.parametrize("cell,kernel,calls,steps", [
    ("box-2d49p.iterate", "sptc_onehot", 7, 25600 // 8),
    ("heat-2d.iterate", "sptc_star", 2, 25600 // 32),
    ("heat-2d.iterate-2x2", "sptc_star", 10, 102400 // 32),
])
def test_box_cell_onehot_grid_fills_the_mxu(cell, kernel, calls, steps, topo,
                                            tpu_compile):
    """The cells' fused kernels take wide grid steps.  The box's one-hot
    kernels take MXU-sized ones: at most 1/8 of the 25,600 steps a call
    that 8-row, 512-lane steps took at 10240².  The heat cells' star
    kernels take steps sized by the VMEM they count: at most 1/32 of those
    steps, and of the 102,400 such steps at the 2×2's 20480² blocks; each
    star call sets its VMEM limit, and the heat programs compile for a
    v5e within it."""
    from repro.kernels.sptc_spmm.kernel import _STAR_VMEM_BUDGET
    traced = _cell_trace(cell, topo)
    eqns = list(_pallas_eqns(traced.jaxpr.jaxpr))
    assert len(eqns) == calls and {e.params["name"] for e in eqns} == {kernel}
    for eqn in eqns:
        grid = eqn.params["grid_mapping"].grid
        assert math.prod(grid) <= steps, grid
    if kernel == "sptc_star":
        for eqn in eqns:
            params = eqn.params["compiler_params"]["mosaic_tpu"]
            assert 0 < params.vmem_limit_bytes <= _STAR_VMEM_BUDGET
        assert "tpu_custom_call" in traced.lower().compile().as_text()


#: cells whose timed program does all its work on the grid in its kernels
_KERNELS_ONLY = {"box-3d27p.iterate"}


@pytest.mark.parametrize("cell,kernel", [
    ("box-2d49p.iterate", "sptc_onehot"),
    ("heat-2d.iterate", "sptc_star"),
    ("heat-2d.iterate-2x2", "sptc_star"),
    ("box-3d27p.iterate", "sptc_onehot3d"),
])
def test_cell_program_is_covered_by_stages(cell, kernel, topo, tpu_compile):
    """Every instruction of the timed program that works on the grid is a
    kernel, a collective or counts under a stage the program declares
    (``bench/scopes.py``), and the fused kernel carries its path's name.
    The 2-D cells' programs hold work besides their kernels; the 3-D
    cell's program holds its kernels alone."""
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
    if cell in _KERNELS_ONLY:
        assert {i.name for i in work} == kernels
    else:
        assert len(work) > len(kernels)
    assert not stray, stray
    assert {where[c] for c in collectives} <= {"halo.exchange"}


#: the HBM a v5e chip's programs may use, as XLA reports it
_V5E_HBM = 15.75 * 2**30


def test_box3d_cell_fits_one_chip_with_one_kernel_a_step(topo, tpu_compile):
    """Box-3D27P's timed program at 1024^3 f32: one ``sptc_onehot3d`` call
    a step and no other Pallas call, no copy, pad or slice of the grid
    anywhere in it, and within one v5e's HBM (arguments, temporaries and
    outputs not aliased to arguments)."""
    from repro.core.stencil import paper_suite
    from bench import harness
    wl = harness.load_workload_file("box-3d27p.iterate")
    spc = int(wl["steps_per_call"])
    traced = _cell_trace("box-3d27p.iterate", topo)
    scans = [e for e in traced.jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == spc
    body = list(_pallas_grids(scans[0].params["jaxpr"].jaxpr))
    assert [name for name, _ in body] == ["sptc_onehot3d"]
    assert [name for name, _ in _pallas_grids(traced.jaxpr.jaxpr)] == ["sptc_onehot3d"]
    compiled = traced.lower().compile()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert used <= _V5E_HBM, used
    spec = next(s for s in paper_suite() if s.name == "box-3d1r")
    side = int(wl["grid"][0]) + 2 * spec.radius
    grid = re.compile(rf"= f32\[{side},{side},{side}\]\S* (\S+)\(")
    ops = {m.group(1) for m in grid.finditer(compiled.as_text())}
    assert ops <= {"parameter", "custom-call", "get-tuple-element", "tuple",
                   "while", "bitcast"}, ops


def _widest_square_plane(r, n_ops):
    """The widest ``w`` whose ``w x w`` planes the planes kernel takes."""
    from repro.kernels.sptc_spmm.kernel import planes_fit
    w = 3
    while planes_fit((64, w + 1, w + 1), jnp.float32, r, n_ops):
        w += 1
    return w


def test_box3d_planes_kernel_compiles_up_to_its_vmem_check(one_chip,
                                                           tpu_compile):
    """The planes kernel's VMEM check is sound at its edge: the widest
    square planes it admits compile for a v5e as one ``sptc_onehot3d``
    call a step.  Planes one row and lane wider run the row ops one by
    one (``sptc_onehot``), and so do 2048^2 planes, which the kernel
    cannot hold; both compile as well."""
    spec = make_stencil("box", 3, 1, seed=52)
    eng = StencilEngine(spec, backend="pallas_sptc")
    w = _widest_square_plane(1, 9)
    for side, kernel in [(w, "sptc_onehot3d"), (w + 1, "sptc_onehot"),
                         (2050, "sptc_onehot")]:
        # deep enough that the compiler keeps the state out of VMEM
        u = jax.ShapeDtypeStruct((64, side, side), jnp.float32,
                                 sharding=one_chip)
        traced = jax.jit(lambda v: eng.iterate(v, 2),
                         donate_argnums=0).trace(u)
        assert {n for n, _ in _pallas_grids(traced.jaxpr.jaxpr)} == {kernel}
        assert "tpu_custom_call" in traced.lower().compile().as_text()
