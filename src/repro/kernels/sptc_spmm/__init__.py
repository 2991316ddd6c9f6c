from repro.kernels.sptc_spmm.ops import sptc_planes, sptc_spmm_fused

__all__ = ["sptc_planes", "sptc_spmm_fused"]
