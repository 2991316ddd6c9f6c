"""Names of the step program's stages.

Each stage is emitted under ``jax.named_scope(<name>)``.  A named scope is
compile-time metadata only: it reaches every compiled instruction of the
stage as ``metadata={op_name=".../<name>/..."}`` and changes no
instruction.  A device profile then says which stage each instruction's
time belongs to; an instruction counts under the innermost of these names
in its ``op_name`` (``bench/scopes.py`` reads them).

The Pallas kernels carry names of their own (``pallas_call(name=...)``):
``sptc_onehot`` and ``sptc_star`` (the fused SpTC kernel's two paths),
``sptc_spmm``, ``mxu_windows``, ``stencil_direct`` and ``conv1d``.
"""
from __future__ import annotations

#: axis moves and transposes around the kernels (``moveaxis``, ``.T``)
LAYOUT = "emit.layout"
#: row-op input slices, the row-op sum and its zeros
ACCUMULATE = "emit.accumulate"
#: a kernel wrapper's own copies: pads to the kernel's window, tiled
#: operands, the crop of its output
KERNEL_PREP = "kernel.prep"
#: the zero re-pad between steps of ``StencilEngine.iterate``
REPAD = "iterate.repad"
#: what feeds the halo collectives: the zero pad, the edge slices, the
#: ``ppermute``s
HALO_EXCHANGE = "halo.exchange"
#: the halo step's copies: the extended-block and rim concatenations, the
#: rim slabs' slices and the phantom-row mask
HALO_STITCH = "halo.stitch"

ALL = (LAYOUT, ACCUMULATE, KERNEL_PREP, REPAD, HALO_EXCHANGE, HALO_STITCH)
