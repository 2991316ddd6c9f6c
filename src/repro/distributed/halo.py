"""Distributed halo-exchange stencil execution (shard_map + ppermute).

Grids that don't fit one device are block-partitioned over a 1-D or 2-D
device mesh; each shard runs the SAME lowered program the single-device
path runs (`core/engine.emit(LoweredPlan)` — the §3.3 zero-overhead
profile holds per shard), and shards exchange width-``k·r`` halos with
``lax.ppermute``:

  * **Both edges per axis, 2 collectives per partitioned axis.** Shard
    ``i`` sends its high edge to ``i+1`` (which receives it as its low
    halo) and its low edge to ``i-1``.  The ``repro.vet`` sharded probe
    certifies exactly 2 collective-permutes per partitioned axis in the
    compiled HLO, and zero all-gathers.

  * **Zero-flux physical boundary for free.** ``ppermute`` fills devices
    that are not a destination of any ``(src, dst)`` pair with zeros —
    exactly the zero-padding convention ``StencilEngine.iterate`` uses
    (``jnp.pad`` re-pad per step), so the outermost shards need no
    special-casing at all.

  * **Compute/communication overlap, structurally.** The local block is
    split into an interior region (computable from resident data alone)
    and rim slabs (need the exchanged halos).  The ``ppermute``s are
    issued *first* and the interior ``emit(plan)`` call consumes only the
    pre-exchange block, so the interior matmuls carry no data dependence
    on the collectives — XLA's latency-hiding scheduler is free to run
    them under the exchange (async collectives on TPU/GPU; on CPU the
    semantics are identical, the overlap is just not observable).

  * **Corner halos ride along.** Axes are exchanged sequentially and the
    second axis sends edges of the *already-extended* array, so diagonal
    neighbours' corner data arrives through two hops — still only 2
    collectives per axis, and box stencils (which read corners) stay
    exact.

  * **Non-divisible grids.** A dim that doesn't divide its mesh axis is
    trailing-padded to the next multiple; a mask built from
    ``lax.axis_index`` zeroes the phantom rows after every step (keeping
    the zero-flux convention exact under ``iterate``) and the output is
    cropped back.

API convention matches :class:`~repro.core.engine.StencilEngine`:
``engine(x)`` consumes a halo-inclusive ``(N+2kr, ...)`` grid and
returns the ``(N, ...)`` interior update; ``iterate(u, steps)`` evolves
a shape-``(N, ...)`` interior grid with zero boundary, keeping all state
device-resident across steps (one scan inside ``shard_map``).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import scopes
from repro.core.engine import emit
from repro.core.ir import LoweredPlan
from repro.core.stencil import StencilSpec
from repro.core.transform import lower_spec

__all__ = ["ShardedStencilEngine", "grid_mesh"]


def grid_mesh(parts, devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D/2-D device mesh for grid partitioning (axes ``sp0``, ``sp1``).

    ``parts`` is the per-axis shard count: ``8`` or ``(8,)`` partitions
    grid axis 0 eight ways; ``(4, 2)`` partitions axes 0 and 1.  Uses the
    first ``prod(parts)`` of ``devices`` (default ``jax.devices()``).
    """
    parts = (int(parts),) if isinstance(parts, int) else tuple(
        int(p) for p in parts)
    if not parts or any(p < 1 for p in parts):
        raise ValueError(f"mesh shape must be positive ints, got {parts}")
    need = math.prod(parts)
    devs = list(jax.devices()) if devices is None else list(devices)
    if len(devs) < need:
        raise ValueError(
            f"mesh {parts} needs {need} devices but only {len(devs)} are "
            f"available (CPU runs can force virtual devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    arr = np.asarray(devs[:need], dtype=object).reshape(parts)
    return Mesh(arr, tuple(f"sp{i}" for i in range(len(parts))))


def _take(x: jnp.ndarray, axis: int, start: int, stop: int) -> jnp.ndarray:
    idx = [slice(None)] * x.ndim
    idx[axis] = slice(start, stop)
    return x[tuple(idx)]


class ShardedStencilEngine:
    """Block-partitioned stencil applicator over a device mesh.

    ``mesh`` may be a :class:`jax.sharding.Mesh` (1 or 2 axes, each
    partitioning one grid axis — by default grid axes ``0, 1`` in mesh
    axis order; override with ``grid_axes``) or an int / tuple of shard
    counts, which is passed to :func:`grid_mesh`.  Mesh axes of extent 1
    are degenerate (no exchange, plain zero padding) and are dropped.

    All plan knobs (``backend``, ``L``, ``fuse_rows``,
    ``temporal_steps``) mean exactly what they mean on ``StencilEngine``;
    the lowering is shared and untouched.  Variable coefficients are not
    supported (the per-field tables are fixed to the global shape and do
    not decompose over blocks).
    """

    def __init__(self, spec: StencilSpec, mesh, *,
                 backend: str = "direct", L: Optional[int] = None,
                 fuse_rows: bool = False, temporal_steps: int = 1,
                 grid_axes: Optional[Sequence[int]] = None) -> None:
        if isinstance(mesh, (int, tuple, list)):
            mesh = grid_mesh(mesh)
        if len(mesh.axis_names) > spec.ndim:
            raise ValueError(
                f"mesh has {len(mesh.axis_names)} axes but {spec.name} is "
                f"only {spec.ndim}-D")
        axes = (tuple(range(len(mesh.axis_names))) if grid_axes is None
                else tuple(int(a) for a in grid_axes))
        if len(axes) != len(mesh.axis_names):
            raise ValueError(
                f"grid_axes {axes} must name one grid axis per mesh axis "
                f"{mesh.axis_names}")
        if len(set(axes)) != len(axes) or not all(
                0 <= a < spec.ndim for a in axes):
            raise ValueError(
                f"grid_axes {axes} must be distinct axes of a "
                f"{spec.ndim}-D grid")
        self.spec = spec
        self.mesh = mesh
        self.backend = backend
        self.temporal_steps = temporal_steps
        #: width of the exchanged halo: k·r (temporal blocking fuses k
        #: steps per exchange — communication amortizes with k)
        self.halo = temporal_steps * spec.radius
        # grid axis -> (mesh axis name, shard count); extent-1 axes are
        # single-device along that dim and need no exchange
        self._part: Dict[int, Tuple[str, int]] = {
            a: (name, int(mesh.shape[name]))
            for a, name in zip(axes, mesh.axis_names)
            if int(mesh.shape[name]) > 1}
        self.plan_ir: LoweredPlan = lower_spec(
            spec, backend=backend, L=L, fuse_rows=fuse_rows,
            temporal_steps=temporal_steps)
        self.L = self.plan_ir.L
        self._step_fn = emit(self.plan_ir)
        entries: list = [None] * spec.ndim
        for a, (name, _) in self._part.items():
            entries[a] = name
        self._pspec = P(*entries)
        self._run = jax.jit(self._run_sharded, static_argnums=1)
        self._fn = jax.jit(self._halo_call)

    @property
    def n_shards(self) -> int:
        """Devices the grid is actually partitioned over."""
        return math.prod(n for _, n in self._part.values()) or 1

    def partition(self) -> Dict[int, int]:
        """Grid axis -> shard count (extent-1 axes omitted)."""
        return {a: n for a, (_, n) in self._part.items()}

    # -- public API ----------------------------------------------------------
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        """Halo-inclusive ``(N+2kr, ...)`` in, interior ``(N, ...)`` out.

        Matches ``StencilEngine.__call__`` to float tolerance (block-local
        GEMM tiling reassociates the reductions).
        """
        return self._fn(x)

    def step(self, u: jnp.ndarray) -> jnp.ndarray:
        """One fused k-step on an interior grid with zero boundary."""
        return self._run(u, 1)

    def iterate(self, u: jnp.ndarray, steps: int) -> jnp.ndarray:
        """Evolve ``steps`` steps, state staying device-resident.

        Equals ``StencilEngine.iterate(jnp.pad(u, kr), steps)`` center-
        cropped: the zero re-pad per scan iteration there is exactly the
        zero-flux halo the exchange provides here.  ``steps`` must be a
        multiple of ``temporal_steps``.
        """
        k = self.temporal_steps
        if steps % k != 0:
            raise ValueError(
                f"steps={steps} must be a multiple of temporal_steps={k}")
        return self._run(u, steps // k)

    # -- implementation ------------------------------------------------------
    def _halo_call(self, x: jnp.ndarray) -> jnp.ndarray:
        # running the zero-flux step on the full halo-inclusive domain and
        # center-cropping is exact: output point p reads inputs within
        # distance k·r, so every surviving point reads only real values
        y = self._run_sharded(x, 1)
        h = self.halo
        return y[(slice(h, -h),) * self.spec.ndim]

    def _geometry(self, gshape: Tuple[int, ...]):
        """Trailing pads to shard-divisible extents + per-axis block sizes."""
        h = self.halo
        pads = [(0, 0)] * self.spec.ndim
        blocks: Dict[int, int] = {}
        for a, (_, n) in self._part.items():
            np_a = -(-gshape[a] // n) * n
            b = np_a // n
            if b <= 2 * h:
                raise ValueError(
                    f"dim {a} of extent {gshape[a]} over {n} shards gives "
                    f"per-device blocks of {b} rows, but the halo needs "
                    f"blocks > 2·k·r = {2 * h} (radius {self.spec.radius} × "
                    f"temporal_steps {self.temporal_steps}); use fewer "
                    f"shards along this axis or a larger grid")
            pads[a] = (0, np_a - gshape[a])
            blocks[a] = b
        return pads, blocks

    def _local_step(self, gshape: Tuple[int, ...], blocks: Dict[int, int]):
        """Per-shard zero-flux step closure for one global geometry."""
        h = self.halo
        d = self.spec.ndim
        part = self._part
        paxes = sorted(part)
        step = self._step_fn

        def fn(u: jnp.ndarray) -> jnp.ndarray:
            # unpartitioned axes take the physical zero boundary directly
            pads = [(0, 0) if a in part else (h, h) for a in range(d)]
            with jax.named_scope(scopes.HALO_EXCHANGE):
                base = jnp.pad(u, pads)
            # issue every exchange first: 2 ppermutes per partitioned
            # axis.  Later axes send edges of the already-extended array
            # so corner halos arrive through two hops (box stencils read
            # them).  Shards with no sending neighbour receive zeros —
            # the zero-flux physical boundary.
            ext = base
            for a in paxes:
                name, n = part[a]
                fwd = [(i, i + 1) for i in range(n - 1)]
                bwd = [(i + 1, i) for i in range(n - 1)]
                size = ext.shape[a]
                with jax.named_scope(scopes.HALO_EXCHANGE):
                    lo = jax.lax.ppermute(_take(ext, a, size - h, size),
                                          name, fwd)
                    hi = jax.lax.ppermute(_take(ext, a, 0, h), name, bwd)
                with jax.named_scope(scopes.HALO_STITCH):
                    ext = jnp.concatenate([lo, ext, hi], axis=a)
            # interior: reads only the pre-exchange block, so it carries
            # no dependence on the collectives and overlaps the exchange
            y = step(base)
            # rim slabs consume the exchanged halos; ext is sliced so each
            # slab's output is exactly the h-deep face along its axis
            for j in reversed(range(len(paxes))):
                a = paxes[j]
                b = blocks[a]
                sl_lo = [slice(None)] * d
                sl_hi = [slice(None)] * d
                for a2 in paxes[:j]:
                    sl_lo[a2] = sl_hi[a2] = slice(h, blocks[a2] + h)
                sl_lo[a] = slice(0, 3 * h)
                sl_hi[a] = slice(b - h, b + 2 * h)
                with jax.named_scope(scopes.HALO_STITCH):
                    y = jnp.concatenate(
                        [step(ext[tuple(sl_lo)]), y, step(ext[tuple(sl_hi)])],
                        axis=a)
            # zero the phantom rows of a non-divisible dim so iterated
            # steps keep reading zero-flux values past the true boundary
            with jax.named_scope(scopes.HALO_STITCH):
                mask = None
                for a in paxes:
                    name, n = part[a]
                    b = blocks[a]
                    if b * n != gshape[a]:
                        gi = jax.lax.axis_index(name) * b + jnp.arange(b)
                        m = (gi < gshape[a]).reshape(
                            (1,) * a + (b,) + (1,) * (d - a - 1))
                        mask = m if mask is None else mask & m
                if mask is not None:
                    y = jnp.where(mask, y, jnp.zeros((), dtype=y.dtype))
            return y

        return fn

    def _run_sharded(self, u: jnp.ndarray, nblocks: int) -> jnp.ndarray:
        if u.ndim != self.spec.ndim:
            raise ValueError(
                f"expected a {self.spec.ndim}-D grid for {self.spec.name}, "
                f"got shape {tuple(u.shape)}")
        gshape = tuple(int(s) for s in u.shape)
        pads, blocks = self._geometry(gshape)
        padded = any(p[1] for p in pads)
        up = jnp.pad(u, pads) if padded else u
        local = self._local_step(gshape, blocks)
        if nblocks == 1:
            body = local
        else:
            def body(blk: jnp.ndarray) -> jnp.ndarray:
                out, _ = jax.lax.scan(
                    lambda c, _: (local(c), None), blk, None, length=nblocks)
                return out
        # Pallas kernels declare no per-axis variance on their outputs,
        # so the variance check is off; the specs state the partition.
        y = jax.shard_map(body, mesh=self.mesh, in_specs=self._pspec,
                          out_specs=self._pspec, check_vma=False)(up)
        if padded:
            y = y[tuple(slice(0, s) for s in gshape)]
        return y
