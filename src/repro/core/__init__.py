"""Core: the paper's contribution — stencil -> 2:4-sparse GEMM transform."""
from repro.core.stencil import StencilSpec, make_stencil, paper_suite
from repro.core.transform import (kernel_matrix, default_l, decompose_rows,
                                  lower_spec)
from repro.core.sparsify import (Sparse24, SparseStencilKernel, encode_24,
                                 decode_24, is_24_sparse, strided_swap_perm,
                                 sparsify_matrices, sparsify_stencil_kernel)
from repro.core.ir import LoweredPlan
from repro.core.engine import StencilEngine, apply_stencil
from repro.core import analysis, sptc

__all__ = [
    "StencilSpec", "make_stencil", "paper_suite", "kernel_matrix",
    "default_l", "decompose_rows", "lower_spec", "Sparse24",
    "SparseStencilKernel", "encode_24", "decode_24", "is_24_sparse",
    "strided_swap_perm", "sparsify_matrices", "sparsify_stencil_kernel",
    "LoweredPlan", "StencilEngine", "apply_stencil",
    "analysis", "sptc",
]
