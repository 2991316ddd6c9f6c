"""Pallas TPU kernels: the fused 2:4 SpTC stencil executors.

TPU has no Sparse Tensor Core, so the kernels realise ``mma.sp``'s
selection (per output row and 4-wide reduction segment, only the two
input rows the 2-bit metadata names contribute) as an in-VMEM
decompression followed by a dense MXU matmul over the free dimension.

``sptc_fused_call`` — one 1-D application (paper §3.3 "zero runtime
overhead"): ONE Pallas program that, per (N-tile, row-tile) grid
step,

  1. DMAs the overlapping input window of its ``B`` output rows straight
     from HBM into VMEM scratch, double-buffered across sequential grid
     steps (the t+1 window prefetches while tile t computes);
  2. folds the strided row swap AND the 2-bit metadata gather into the
     decompression's comparison positions — the swap permutation is the
     closed form ``p odd: p <-> p±L`` so it is derived from an iota
     inside the kernel, and the metadata is unpacked in-register from
     the packed ``meta_bits`` words.  Nothing is permuted or gathered
     outside the kernel;
  3. runs the dense MXU matmul (f32, or bf16 inputs with f32
     accumulation via ``compute_dtype="bfloat16"``).

``star_fast`` names the program: ``False`` is the one-hot kernel above
(``sptc_onehot``); ``True`` is the star kernel (``sptc_star``), for an
operand whose composed swap∘meta gather is the identity band of the taps
(``core.sparsify.contiguous_band_values``, which every star row op is):
the metadata carries no information, so it skips the decompression and
performs K/2 shifted VPU FMAs over the banded value layout.  The engine
picks it from the plan's decomposition mode.

Blocking: the compressed operand (M = L, K/2) and metadata words are tiny
and live whole in VMEM; the input stays in HBM (``pl.ANY``) because the
overlapping windows cannot be expressed as disjoint BlockSpec tiles.  One
grid step stacks ``B // L`` row tiles of ``L`` output rows, DMAs the
``rows`` window rows they read (``B + L``, rounded up to the sublane tile)
from row ``t·B``, and writes a (B, bn) output tile; ``B`` is a multiple of
``lcm(L, sublane tile)`` (8 rows of f32, 16 of bf16), so every block and
DMA offset is tile-aligned.  The one-hot path sizes the step for the MXU:
``B`` is the largest such multiple whose window fits one 128-row MXU
contraction tile (120 rows for L = 4, 6, 8 in f32; 112 for L = 8 in
bf16), clamped to the output's extent, so one ``(B, rows) x (rows, bn)``
dot serves ~120 streamed rows per latched weight chunk and reads each
input row ~1.07 times; ``bn`` splits the lane extent into equal
128-multiples of at most 2048.  The star path has no MXU tile to fill:
its ``B`` is the largest such multiple whose step (window slots, output
blocks, the f32 accumulator and shifted window slices) ``star_vmem_bytes``
counts within ``_STAR_VMEM_BUDGET``, clamped to the output the same way,
and the call sets its VMEM limit from that count: 384 rows of 2048 lanes
at 10240², 135 grid steps a call.  Its last step's window holds only the
rows its outputs read, so the wrapper pads no more rows than steps of
``lcm(L, sublane tile)`` rows would, and its output is exactly
``(n_out, C)``, the last blocks' writes clipped, so nothing is cropped.
A leading batch grid axis serves ``vmap`` (``common.fold_vmap``).

``sptc_planes_call`` — every row op of a radius-1 3-D box step in one
program (``sptc_onehot3d``) over the state's own layout; see its section.

Both ``*_call`` entry points resolve ``interpret=None`` through
``common.default_interpret()`` at call time: compiled Mosaic on a real
TPU, interpret mode elsewhere, overridable via ``REPRO_PALLAS_INTERPRET``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scopes import KERNEL_PREP
from repro.kernels import common
from repro.kernels.common import round_up


# ---------------------------------------------------------------------------
# one 1-D application: window DMA + in-kernel swap/gather + MXU matmul
# ---------------------------------------------------------------------------

def _fused_kernel(x_hbm, vals_ref, meta_ref, y_ref, scratch, sem,
                  *onehot_scratch,
                  tiles: int, L: int, rows: int, last: int, bn: int,
                  star_fast: bool, compute):
    """One grid step computes ``B`` output rows — ``B // L`` stacked row
    tiles of ``L`` — of batch item ``b`` from a ``rows``-row window
    starting at row ``t·B``.

    ``B`` and ``rows`` are multiples of the sublane tile, so the output
    block, the window DMA and its row offset are all (8, 128)-aligned.
    The last step's window holds only the ``last`` rows its outputs read;
    the window rows below them are left over from an earlier step and
    reach only output rows past ``n_out``, which are never stored.
    On the star path ``vals_ref`` holds the operand's L rows repeated per
    stacked tile (``B`` rows) and ``meta_ref`` is not read; the one-hot
    path takes the L rows once and stacks them as it decompresses into its
    one VMEM scratch buffer, the (B, rows) operand ``w_ref``.
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    t = pl.program_id(2)
    B, kh = y_ref.shape[0], vals_ref.shape[1]

    def dma(slot, tt, n=rows):
        dst = scratch.at[slot] if n == rows else scratch.at[slot, pl.ds(0, n)]
        return pltpu.make_async_copy(
            x_hbm.at[b, pl.ds(pl.multiple_of(tt * B, B), n),
                     pl.ds(pl.multiple_of(j * bn, bn), bn)],
            dst, sem.at[slot])

    def each(slot, tt, op):
        """``op`` on the DMA of tile ``tt``'s window into ``slot``."""
        if last == rows or (isinstance(tt, int) and tt < tiles - 1):
            op(dma(slot, tt))
        elif tiles == 1:
            op(dma(slot, tt, last))
        else:
            pl.when(tt == tiles - 1)(lambda: op(dma(slot, tt, last)))
            pl.when(tt < tiles - 1)(lambda: op(dma(slot, tt)))

    start, wait = (lambda d: d.start()), (lambda d: d.wait())

    # cross-grid-step double buffering: scratch persists across the
    # sequential row-tile axis (grid iterates it fastest), so tile t+1's
    # window streams from HBM while tile t computes.
    @pl.when(t == 0)
    def _():
        each(0, 0, start)

    @pl.when(t + 1 < tiles)
    def _():
        each((t + 1) % 2, t + 1, start)

    each(t % 2, t, wait)
    win = scratch[t % 2]                     # (rows, bn)
    vals = vals_ref[:]                       # (B, K/2)
    if compute is not None:
        win = win.astype(compute)
        vals = vals.astype(compute)
    vals = vals.astype(jnp.float32)
    if star_fast:
        # banded value layout: row i's slot off reads window row i + off —
        # no metadata, K/2 shifted VPU FMAs with f32 accumulation.
        acc = jnp.zeros((B, bn), dtype=jnp.float32)
        for jj in range(kh):
            acc = acc + vals[:, jj:jj + 1] * \
                win[jj:jj + B, :].astype(jnp.float32)
        y_ref[:] = acc.astype(y_ref.dtype)
        return
    (w_ref,) = onehot_scratch

    # the decompressed operand depends only on the values and metadata:
    # build it once per sweep of the sequential row-tile axis
    @pl.when(t == 0)
    def _():
        w_ref[:] = _decompress(vals, meta_ref[:], B, L, rows).astype(
            w_ref.dtype)

    y_ref[:] = jnp.dot(w_ref[:], win, precision=common.dot_precision(win.dtype),
                       preferred_element_type=jnp.float32
                       ).astype(y_ref.dtype)


def _decompress(vals, words, B: int, L: int, rows: int, shift: int = 0):
    """Dense (B, rows) band of ``B // L`` stacked tiles of the operand,
    from its (L, K/2) values and (L, nwords) packed metadata words.

    Output row ``i`` reads window rows ``i + shift`` onwards; a column
    that falls outside ``[0, rows)`` is dropped.
    """
    kh = vals.shape[1]
    # unpack the 2-bit metadata from the packed words in-register
    jl = jax.lax.broadcasted_iota(jnp.int32, (L, kh), 1)
    exp = jnp.zeros((L, kh), jnp.uint32)
    for w in range(words.shape[1]):
        exp = jnp.where(jl // 16 == w, words[:, w:w + 1], exp)
    meta_l = (jax.lax.shift_right_logical(exp, (2 * (jl % 16)).astype(
        jnp.uint32)) & 3).astype(jnp.int32)
    # stack the operand's L rows once per tile of the step
    i = jax.lax.broadcasted_iota(jnp.int32, (B, kh), 0)
    tile = i // L
    stacked = jnp.zeros((B, kh), jnp.float32)
    meta = jnp.zeros((B, kh), jnp.int32)
    for l in range(L):
        row = i - tile * L == l
        stacked = jnp.where(row, vals[l:l + 1, :], stacked)
        meta = jnp.where(row, meta_l[l:l + 1, :], meta)
    jj = jax.lax.broadcasted_iota(jnp.int32, (B, kh), 1)
    gidx = 4 * (jj // 2) + meta              # swapped-window position
    # strided swap folded into the decompression positions: the swap
    # "odd p exchanges halves" is an involution, so swapped position g
    # reads window row kpos(g) — derived from an iota, zero loads and
    # zero stores (§3.3).  Stacked tile s reads its window from row s·L.
    kpos = jnp.where(gidx % 2 == 1,
                     jnp.where(gidx < L, gidx + L, gidx - L), gidx)
    col = tile * L + kpos                    # (B, K/2) window column
    if shift:
        col = col + shift
    q = jax.lax.broadcasted_iota(jnp.int32, (B, rows), 1)
    w_dense = jnp.zeros((B, rows), jnp.float32)
    for s in range(kh):                      # one-hot decompression
        w_dense = w_dense + jnp.where(q == col[:, s:s + 1],
                                      stacked[:, s:s + 1], 0.0)
    return w_dense


#: widest lane block of a fused grid step
_MAX_BN = 2048


def _sublanes(dtype) -> int:
    """Rows of one (sublane, 128) VMEM tile of ``dtype``: 8 f32, 16 bf16."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _fused_geometry(L: int, n_out: int, c: int, dtype, compute,
                    star_fast: bool) -> "tuple[int, int, int]":
    """(output rows per grid step ``B``, window rows, lane block ``bn``).

    ``B`` is a multiple of ``lcm(L, sub)``, ``sub`` the sublane tile of the
    input ``dtype`` (and, on the one-hot path, of the ``compute`` dtype);
    the window covers the ``B + L`` rows those outputs read, rounded up to
    ``sub``, and ``bn`` splits the lane extent into equal 128-multiples of
    at most ``_MAX_BN``.  The one-hot path takes the largest ``B`` whose
    window fits one MXU contraction tile; the star path, which has no MXU
    tile to fill, the largest whose step ``star_vmem_bytes`` counts within
    ``_STAR_VMEM_BUDGET``.  Both then take the least ``B`` that covers
    ``n_out`` in as many steps, so a short output gets one step of
    ``round_up(n_out, lcm)`` rows, and a narrow one tall steps over 128
    lanes.
    """
    lanes = round_up(c, common.LANES)
    nj = -(-lanes // _MAX_BN)
    bn = round_up(-(-lanes // nj), common.LANES)
    sub = _sublanes(dtype)
    if not star_fast:
        sub = max(sub, _sublanes(compute or dtype))
    m = L * sub // math.gcd(L, sub)
    if star_fast:
        B = m
        while star_vmem_bytes(B + m, round_up(B + m + L, sub), bn, L, dtype,
                              compute) <= _STAR_VMEM_BUDGET:
            B += m
    else:
        B = max(m, (common.MXU - L) // m * m)
    B = round_up(-(-n_out // -(-n_out // B)), m)
    return B, round_up(B + L, sub), bn


@functools.partial(jax.jit, static_argnames=(
    "n_out", "L", "star_fast", "compute_dtype", "interpret"))
def _sptc_fused_jit(x3d, values, meta_bits, *, n_out: int, L: int,
                    star_fast: bool, compute_dtype, interpret: bool):
    nb, rows_in, c = x3d.shape
    compute = jnp.dtype(compute_dtype) if compute_dtype else None
    B, rows, bn = _fused_geometry(L, n_out, c, x3d.dtype, compute, star_fast)
    tiles = -(-n_out // B)
    c_pad = round_up(c, bn)
    last, out, params = rows, (nb, tiles * B, c_pad), None
    if star_fast:
        # the last window reads only the rows its outputs need, and the
        # output is exactly (n_out, c): the last blocks' writes are clipped
        last = min(rows, round_up(n_out - (tiles - 1) * B + L,
                                  _sublanes(x3d.dtype)))
        out = (nb, n_out, c)
        params = pltpu.CompilerParams(vmem_limit_bytes=star_vmem_bytes(
            B, rows, bn, L, x3d.dtype, compute))
    need = (tiles - 1) * B + last
    with jax.named_scope(KERNEL_PREP):
        if need > rows_in or c_pad != c:
            x3d = jnp.pad(x3d, ((0, 0), (0, max(0, need - rows_in)),
                                (0, c_pad - c)))
        if star_fast:
            values = jnp.tile(values, (B // L, 1))
    kern = functools.partial(_fused_kernel, tiles=tiles, L=L, rows=rows,
                             last=last, bn=bn, star_fast=star_fast,
                             compute=compute)
    scratch = [pltpu.VMEM((2, rows, bn), x3d.dtype),
               pltpu.SemaphoreType.DMA((2,))]
    if not star_fast:
        scratch.append(pltpu.VMEM((B, rows), compute or x3d.dtype))
    y = pl.pallas_call(
        kern,
        name="sptc_star" if star_fast else "sptc_onehot",
        grid=(nb, c_pad // bn, tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),             # input in HBM
            pl.BlockSpec(values.shape, lambda b, j, t: (0, 0)),
            pl.BlockSpec(meta_bits.shape, lambda b, j, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, B, bn), lambda b, j, t: (b, t, j)),
        out_shape=jax.ShapeDtypeStruct(out, x3d.dtype),
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
    )(x3d, values, meta_bits)
    with jax.named_scope(KERNEL_PREP):
        return y[:, :n_out, :c]


def sptc_fused_call(values, meta_bits, x2d, *, n_out: int, L: int,
                    star_fast: bool,
                    compute_dtype: str | None = None,
                    interpret: bool | None = None):
    """Fused stencil SpMM: y[i] = sum_j band(i, j) * x2d[i + ...].

    ``values``    (L, K/2) compressed operand — the banded layout from
                  ``contiguous_band_values`` when ``star_fast=True``.
    ``meta_bits`` (L, ceil(K/32)) packed uint32 metadata words.
    ``x2d``       (>= n_out + L, C) input rows, UNswapped — the swap
                  happens inside the kernel.
    Returns the (n_out, C) stencil output.  ``vmap`` over ``x2d`` runs as
    the kernel's own batch grid axis (``common.fold_vmap``).
    """
    if interpret is None:
        interpret = common.default_interpret()
    call = functools.partial(_sptc_fused_jit, n_out=n_out, L=L,
                             star_fast=star_fast,
                             compute_dtype=compute_dtype, interpret=interpret)
    return common.fold_vmap(call)(x2d, values, meta_bits)


# ---------------------------------------------------------------------------
# VMEM: what a grid step of the star path and of the planes kernel holds
# ---------------------------------------------------------------------------

#: most VMEM the planes kernel may ask for (v5e holds 128 MiB)
_PLANES_VMEM_CAP = 100 << 20
#: most VMEM a star grid step may count (``star_vmem_bytes``): past about
#: 256 rows of 2048 lanes a call runs within 1% of its fastest
_STAR_VMEM_BUDGET = 32 << 20
#: room beside the counted buffers and tile values, for Mosaic's own
#: scratch and the rounding of its allocations
_MOSAIC_SCRATCH = 4 << 20


def star_vmem_bytes(B: int, rows: int, bn: int, kh: int, dtype,
                    compute=None) -> int:
    """Scoped VMEM a star grid step takes: ``B`` output rows from a
    ``rows``-row window, ``bn`` lanes, ``kh`` taps.

    The two window slots and the two output blocks in ``dtype``; the f32
    accumulator and the ``kh`` shifted window slices the FMA loop
    materialises (and the window cast to f32 where the input or the
    ``compute`` dtype is not f32); the (B, kh) values, two blocks padded
    to 128 lanes; and ``_MOSAIC_SCRATCH``.  In compiles for a described
    v5e that bounds the kernel's need from 8 x 128 to 10240 x 128 and
    512 x 2048 steps, f32 and bf16.
    """
    item = jnp.dtype(dtype).itemsize
    f32 = (kh + 1) * B
    if item != 4 or compute is not None:
        f32 += rows
    return ((2 * (rows + B) * item + f32 * 4) * bn
            + 2 * B * common.LANES * 4 + _MOSAIC_SCRATCH)


def planes_vmem_bytes(shape, dtype, r: int, n_ops: int) -> int:
    """Scoped VMEM the planes kernel takes on a ``(Z, Y, X)`` state.

    Its double-buffered input and output planes, the decompressed bands,
    and the f32 values of each row-tile body, which Mosaic keeps in VMEM
    once per run of tiles (each run's body is its own code): ``n_ops +
    2(2r + 1)`` values of ``B`` rows and the ``2r + 1`` windows, each as
    wide as the plane.  In compiles for a v5e that bounds the kernel's
    need on planes from 10 x 32768 and 18 x 32768 to 4096 x 258 and
    1026 x 2050; ``_MOSAIC_SCRATCH`` is the room above it.
    """
    ny, nx = shape[-2:]
    by, bx = round_up(ny, common.SUBLANES), round_up(nx, common.LANES)
    B, rows, runs, shifts = _planes_geometry(r, ny)
    n_in = 2 * r + 1
    item = jnp.dtype(dtype).itemsize
    return (2 * (n_in + 1) * by * bx * item
            + len(shifts) * n_ops * B * rows * item
            + len(runs) * ((n_ops + 2 * n_in) * B + n_in * rows) * bx * 4
            + _MOSAIC_SCRATCH)


def planes_fit(shape, dtype, r: int, n_ops: int) -> bool:
    """Whether the planes kernel's VMEM holds a ``(Z, Y, X)`` state."""
    return planes_vmem_bytes(shape, dtype, r, n_ops) <= _PLANES_VMEM_CAP


# ---------------------------------------------------------------------------
# 3-D planes: every row op of a 3-D box step in one program
# ---------------------------------------------------------------------------

def _planes_geometry(r: int, ny: int):
    """(output rows per tile ``B``, window rows, tile runs, band shifts).

    A run ``(o, s, j, n)`` is ``n`` tiles; its ``k``-th writes rows
    ``[o + kB, o + kB + B)`` of an output plane from the window rows
    ``[s + kB, s + kB + rows)`` of each input plane, through the band
    decompressed with shift ``shifts[j]``.  ``B`` is the widest multiple
    of the sublane tile whose ``B + 2r`` window fits one MXU contraction
    tile; tiles start on sublane tiles and cover the plane's rows rounded
    up to a sublane tile, the last one flush with that end.  Windows start
    ``r`` rows above their tile and are clamped into the plane, so the
    first and last tiles read their band shifted; the tiles between them
    form one run of shift 0, whose windows lie inside the plane's block.
    """
    by = round_up(ny, common.SUBLANES)
    B = min((common.MXU - 2 * r) // common.SUBLANES * common.SUBLANES, by)
    rows = min(round_up(B + 2 * r, common.SUBLANES), ny)
    pre = round_up(r, common.SUBLANES)
    runs, shifts = [], []
    for t in range(-(-by // B)):
        o = min(t * B, by - B)
        s = min(max(o - r, 0), ny - rows)
        h = o - s - r
        for p in range(max(o, r), min(o + B, ny - r)):
            assert s <= p - r and p + r < s + rows, (r, ny, o, s, p)
        if h not in shifts:
            shifts.append(h)
        if (runs and h == 0 and shifts[runs[-1][2]] == 0
                and o == runs[-1][0] + runs[-1][3] * B
                and o >= pre and o + rows <= by):
            o0, s0, j, n = runs[-1]
            runs[-1] = (o0, s0, j, n + 1)
        else:
            runs.append((o, s, shifts.index(h), 1))
    return B, rows, tuple(runs), tuple(shifts)


def _planes_kernel(*refs, leads, r: int, L: int, B: int, rows: int, runs,
                   shifts, ny: int, nx: int, nz: int):
    """Output plane ``z`` of a 3-D box step, halo included.

    ``refs`` opens with the ``2r + 1`` input planes ``z - r .. z + r``
    (clamped into the grid).  Row op ``i`` with lead ``(dz, dx)`` runs the
    operand's band down the plane's rows (``y``) of input plane ``dz``;
    ops of one ``dx`` share a lane shift of ``r - dx``, taken once on
    their summed dots.  Halo planes, rows and lanes are written as zeros,
    so the output is the next step's state.
    """
    n_in = 2 * r + 1
    planes = refs[:n_in]
    vals_ref, meta_ref, y_ref, w_ref = refs[n_in:]
    z = pl.program_id(0)

    # the decompressed bands depend only on the operands: build them at
    # the first grid step, one set per band shift
    @pl.when(z == 0)
    def _():
        vals = vals_ref[:].astype(jnp.float32)
        words = meta_ref[:]
        for j, h in enumerate(shifts):
            for i in range(len(leads)):
                w_ref[j, i] = _decompress(
                    vals[i * L:(i + 1) * L], words[i * L:(i + 1) * L],
                    B, L, rows, h).astype(w_ref.dtype)

    halo = (z < r) | (z >= nz - r)

    @pl.when(halo)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(jnp.logical_not(halo))
    def _():
        bx = y_ref.shape[1]
        groups: dict = {}
        for i, (dz, dx) in enumerate(leads):
            groups.setdefault(dx, []).append((i, dz))
        used = sorted({dz for dz, _ in leads})
        row = jax.lax.broadcasted_iota(jnp.int32, (B, bx), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (B, bx), 1)
        lane_ok = (lane >= r) & (lane < nx - r)
        pre = round_up(r, common.SUBLANES)

        def tile(o, wins, j):
            acc = None
            for dx, ops in sorted(groups.items()):
                part = None
                for i, dz in ops:
                    d = jnp.dot(w_ref[j, i], wins[dz],
                                precision=common.dot_precision(wins[dz].dtype),
                                preferred_element_type=jnp.float32)
                    part = d if part is None else part + d
                # output lane x reads input lane x + dx - r
                shift = (r - dx) % bx
                if shift:
                    part = pltpu.roll(part, shift, 1)
                acc = part if acc is None else acc + part
            ok = lane_ok & (row + o >= r) & (row + o < ny - r)
            y_ref[pl.ds(o, B), :] = jnp.where(ok, acc, 0.0).astype(y_ref.dtype)

        for o0, s0, j, n in runs:
            if n == 1:
                tile(o0, {dz: planes[dz][s0:s0 + rows, :] for dz in used}, j)
                continue

            # a run's windows start r rows above its tiles: load from the
            # sublane tile above, then drop the rows before the window
            def body(k, carry, o0=o0, j=j):
                o = pl.multiple_of(o0 + k * B, common.SUBLANES)
                wins = {dz: planes[dz][pl.ds(o - pre, rows + pre), :]
                        [pre - r:pre - r + rows] for dz in used}
                tile(o, wins, j)
                return carry
            jax.lax.fori_loop(0, n, body, 0)


def _plane_index(z, *, d: int, nz: int):
    return jnp.clip(z + d, 0, nz - 1), 0, 0


@functools.partial(jax.jit, static_argnames=("leads", "L", "r", "interpret"))
def _planes_jit(x, values, meta_bits, *, leads, L: int, r: int,
                interpret: bool):
    nz, ny, nx = x.shape
    by, bx = round_up(ny, common.SUBLANES), round_up(nx, common.LANES)
    B, rows, runs, shifts = _planes_geometry(r, ny)
    n_in = 2 * r + 1
    if not planes_fit(x.shape, x.dtype, r, len(leads)):
        raise ValueError(
            f"a {ny} x {nx} plane is too large for the planes kernel: it "
            f"holds {2 * (n_in + 1)} planes in VMEM "
            f"({planes_vmem_bytes(x.shape, x.dtype, r, len(leads))} bytes, "
            f"at most {_PLANES_VMEM_CAP})")
    kern = functools.partial(_planes_kernel, leads=leads, r=r, L=L, B=B,
                             rows=rows, runs=runs, shifts=shifts, ny=ny,
                             nx=nx, nz=nz)
    plane = [pl.BlockSpec((None, by, bx),
                          functools.partial(_plane_index, d=d - r, nz=nz))
             for d in range(n_in)]
    return pl.pallas_call(
        kern,
        name="sptc_onehot3d",
        grid=(nz,),
        in_specs=plane + [
            pl.BlockSpec(values.shape, lambda z: (0, 0)),
            pl.BlockSpec(meta_bits.shape, lambda z: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, by, bx), lambda z: (z, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((len(shifts), len(leads), B, rows),
                                   x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_PLANES_VMEM_CAP),
        interpret=interpret,
    )(*([x] * n_in), values, meta_bits)


def sptc_planes_call(values, meta_bits, x, *, leads, L: int, r: int,
                     interpret: bool | None = None):
    """One step of a 3-D stencil in the state's own layout.

    ``x``         (Z + 2r, Y + 2r, X + 2r) state, halo included.
    ``leads``     per row op, its ``(dz, dx)`` offsets (0 .. 2r): op ``i``
                  runs along ``y`` on input plane ``z + dz - r``, lanes
                  shifted by ``dx - r``.
    ``values``    (n_ops * L, K/2) compressed operands, op after op.
    ``meta_bits`` (n_ops * L, ceil(K/32)) their packed metadata words.
    Returns the next state, the same shape as ``x``, with a zero halo.
    ``vmap`` over ``x`` maps the call over the batch.
    """
    if interpret is None:
        interpret = common.default_interpret()
    call = functools.partial(_planes_jit, leads=tuple(leads), L=L, r=r,
                             interpret=interpret)

    @jax.custom_batching.custom_vmap
    def f(x, values, meta_bits):
        return call(x, values, meta_bits)

    @f.def_vmap
    def _rule(axis_size, in_batched, x, values, meta_bits):
        if any(in_batched[1:]):
            raise NotImplementedError(
                "only the kernel's input array may be vmapped")
        if not in_batched[0]:
            x = jnp.broadcast_to(x, (axis_size,) + x.shape)
        return jax.lax.map(lambda u: call(u, values, meta_bits), x), True

    return f(x, values, meta_bits)
