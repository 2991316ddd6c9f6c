"""Paper Figure 9: throughput (GStencils/s) across stencil shapes.

The paper measures GPU kernels; this container is CPU-only, so we measure
the jit-compiled CPU executables of each execution paradigm — the RELATIVE
ordering and the analytic projection are the reproducible content:

  direct   pointwise shifted FMA          (CUDA-core baseline analogue)
  gemm     dense kernel-matrix GEMM       (TCStencil/dense-TC analogue —
                                           carries the 2x padded-zero MACs)
  sptc     2:4-compressed execution       (SPTCStencil: halved reduction)

plus the ANALYTIC TPU projection: MAC counts from core/analysis scaled by
v5e peak — the number the roofline table cross-checks. Pallas kernels are
excluded here (interpret=True is a correctness harness, not a timer).
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import StencilEngine
from repro.core.stencil import PAPER_SUITE, make_stencil

SIZES_1D = 1_048_576            # ~1M points, paper uses 10.24M
SIZES_2D = (1024, 1024)         # paper uses 10240^2; CPU-scaled


def bench_engine(eng: StencilEngine, x, iters: int = 5) -> float:
    y = eng(x)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(iters):
        y = eng(x)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / iters


def run(iters: int = 5) -> List[Dict]:
    rows = []
    for shape, ndim, r in PAPER_SUITE:
        spec = make_stencil(shape, ndim, r, seed=17 * ndim + r)
        if ndim == 1:
            dims = (SIZES_1D,)
        else:
            dims = SIZES_2D
        pts = float(np.prod(dims))
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=tuple(s + 2 * r for s in dims)).astype(np.float32))
        row = {"stencil": spec.name, "points": pts}
        for backend in ("direct", "gemm", "sptc"):
            eng = StencilEngine(spec, backend=backend)
            dt = bench_engine(eng, x, iters)
            row[f"{backend}_gstencils"] = pts / dt / 1e9
        # §Perf D: fused-rows execution (box-2D GEMM/SpTC paths)
        for backend in ("gemm", "sptc"):
            eng = StencilEngine(spec, backend=backend, fuse_rows=True)
            dt = bench_engine(eng, x, iters)
            row[f"{backend}_fused_gstencils"] = pts / dt / 1e9
        # analytic TPU projection (compute-term GStencils/s at v5e peak)
        taps = spec.taps
        dense_k = 2 * (2 * r + 2)          # padded GEMM reduction width
        row["tpu_dense_proj"] = 197e12 / (2 * dense_k * (taps / (2 * r + 1))) / 1e9
        row["tpu_sptc_proj"] = row["tpu_dense_proj"] * 2
        rows.append(row)
    return rows


def main():
    print("# Fig 9 — stencil throughput by execution paradigm (CPU measured"
          " + TPU analytic projection)")
    rows = run()
    cols = ["stencil", "direct_gstencils", "gemm_gstencils",
            "sptc_gstencils", "gemm_fused_gstencils",
            "sptc_fused_gstencils", "tpu_dense_proj", "tpu_sptc_proj"]
    print(",".join(cols))
    for row in rows:
        print(",".join(f"{row[c]:.3f}" if isinstance(row[c], float)
                       else str(row[c]) for c in cols))
    sp = [r["sptc_gstencils"] / r["gemm_gstencils"] for r in rows]
    print(f"# sptc vs dense-gemm speedup (CPU, semantic): "
          f"geomean {float(np.exp(np.mean(np.log(sp)))):.2f}x")


if __name__ == "__main__":
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache()
    main()
