"""Entry ``iterate_sharded``: a grid too large for one chip, on a mesh.

``ShardedStencilEngine(spec, grid_mesh(mesh), backend).iterate(u,
steps_per_call)`` under one ``jax.jit`` with the state donated.  The
interior state is made partitioned on the mesh from the seed; halos are
exchanged by the program's collectives.  The check runs the plain
reference partitioned the same way (XLA's partitioner exchanges its
halos), so shard edges and corners are compared with every other point.

Workload keys as for ``iterate``, plus ``mesh`` (shards per grid axis).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.entries import iterate as iterate_entry


class Entry(iterate_entry.Entry):

    def __init__(self, ctx) -> None:
        self.mesh_shape = tuple(int(m) for m in ctx.workload["mesh"])
        self.chips_used = math.prod(self.mesh_shape)
        super().__init__(ctx)
        from repro.distributed.halo import grid_mesh
        self.mesh = grid_mesh(self.mesh_shape, devices=self.devices)
        self.sharding = NamedSharding(self.mesh, P(*self.mesh.axis_names))

    def initial(self):
        return self.initial_interior()

    def build(self, u):
        from repro.distributed.halo import ShardedStencilEngine
        eng = ShardedStencilEngine(self.spec, self.mesh, backend=self.wl["backend"])
        spc = self.spc
        return jax.jit(lambda v: eng.iterate(v, spc), donate_argnums=0
                       ).lower(u).compile()

    def interior(self, u):
        return u
