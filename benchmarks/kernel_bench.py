"""Kernel-level microbench: the three stencil execution paradigms at the
SpMM level (what §3.4's kernel engineering targets), CPU wall-clock.

Measures the jnp (XLA-compiled) forms — the Pallas kernels run in
interpret mode off-TPU (correctness harness, Python speed); their rows
report correctness vs the direct oracle plus the **TPU v5e roofline
time** the fused program targets (``roofline/analysis.py``), with the
interpret-mode wall clock recorded only for provenance.  Records the
results as a **versioned JSON artifact** (``BENCH_kernels.json``)
mirroring ``serving_bench.py``'s ``BENCH_serving.json``: per-radius
dense-GEMM vs compressed 2:4 SpMM time and useful-MAC throughput, the
fused pallas_sptc v2 kernel sweep (general / star-fast / bf16 paths vs
the direct oracle, registry × radius/L), plus the end-to-end
tuned-vs-default engine comparison per stencil.

    PYTHONPATH=src python benchmarks/kernel_bench.py --out BENCH_kernels.json
    PYTHONPATH=src python benchmarks/kernel_bench.py --quick   # CI profile
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparsify import sparsify_stencil_kernel
from repro.core.sptc import sptc_matmul
from repro.core.transform import kernel_matrix

SCHEMA = "repro/bench_kernels"
VERSION = 3


def bench(fn, *args, iters=20):
    y = fn(*args)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(iters):
        y = fn(*args)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / iters


def spmm_sweep(radii, n, iters, seed=0):
    """Per-radius dense padded GEMM vs compressed 2:4 SpMM rows."""
    rng = np.random.default_rng(seed)
    rows = []
    for r in radii:
        w = rng.normal(size=2 * r + 1)
        sk = sparsify_stencil_kernel(w)
        L = sk.L
        K = jnp.asarray(kernel_matrix(w, L=L, pad_width=True), jnp.float32)
        x = jnp.asarray(rng.normal(size=(2 * L, n)), jnp.float32)
        vals = jnp.asarray(sk.values, jnp.float32)
        meta = jnp.asarray(sk.meta)
        xp = x[np.asarray(sk.perm)]

        dense = jax.jit(lambda K, x: K @ x)
        sptc = jax.jit(sptc_matmul)
        td = bench(dense, K, x, iters=iters)
        ts = bench(sptc, vals, meta, xp, iters=iters)
        dmacs = L * 2 * L * n
        smacs = L * L * n
        rows.append({
            "radius": r, "L": L, "n": n,
            "dense_us": round(td * 1e6, 1),
            "sptc_us": round(ts * 1e6, 1),
            "dense_gmacs": round(dmacs / td / 1e9, 2),
            "sptc_gmacs": round(smacs / ts / 1e9, 2),
        })
    return rows


def fused_kernel_sweep(radii, n, seed=2):
    """Fused pallas_sptc v2 vs the direct oracle.

    All three kernel paths (general one-hot, star-fast banded, bf16
    compute) run in interpret mode and are checked allclose against the
    NumPy direct stencil.  Device time and roofline shares come from the
    chip benchmark (``bench/``), not from this wall clock.
    """
    from repro.core.sparsify import sparsify_stencil_kernel
    from repro.kernels.sptc_spmm.ops import sptc_spmm_fused
    rng = np.random.default_rng(seed)
    rows = []
    for r in radii:
        w = rng.normal(size=2 * r + 1)
        sk = sparsify_stencil_kernel(w)
        L = sk.L
        n_out = 4 * L
        x = rng.normal(size=(n_out + 2 * r, n)).astype(np.float32)
        want = np.stack([np.tensordot(w, x[i:i + 2 * r + 1], axes=(0, 0))
                         for i in range(n_out)])
        x2 = jnp.asarray(x)

        def run_path(star_fast, compute_dtype=None):
            fn = lambda: sptc_spmm_fused(
                sk.sparse, sk.perm, x2, n_out=n_out, L=L,
                star_fast=star_fast, compute_dtype=compute_dtype)
            t = bench(lambda: fn(), iters=3)
            err = float(np.max(np.abs(np.asarray(fn()) - want)))
            return t, err

        t_gen, err_gen = run_path(False)
        t_star, err_star = run_path("auto")
        _, err_bf16 = run_path("auto", "bfloat16")
        tol = 2e-4 * max(1.0, float(np.max(np.abs(want))))
        rows.append({
            "radius": r, "L": L, "n_out": n_out, "n": n,
            "general_ok": bool(err_gen <= tol),
            "star_fast_ok": bool(err_star <= tol),
            "bf16_ok": bool(err_bf16 <= 0.05 * max(
                1.0, float(np.max(np.abs(want))))),
            "max_err_f32": round(max(err_gen, err_star), 8),
            "max_err_bf16": round(err_bf16, 6),
            "interp_cpu_us": round(t_star * 1e6, 1),
        })
    return rows


def fused_engine_sweep(points, n, seed=3):
    """Engine-level pallas_sptc (fused v2) vs the direct oracle, over the
    stencil registry (shape × ndim) × radius — each point reports the
    plan's L and the max abs error."""
    from repro.core.engine import StencilEngine
    from repro.core.stencil import make_stencil
    rng = np.random.default_rng(seed)
    rows = []
    for shape, ndim, r in points:
        spec = make_stencil(shape, ndim, r, seed=11)
        dims = (n + 2 * r,) * ndim
        x = jnp.asarray(rng.normal(size=dims), jnp.float32)
        want = np.asarray(StencilEngine(spec, backend="direct")(x))
        eng = StencilEngine(spec, backend="pallas_sptc")
        got = np.asarray(eng(x))
        err = float(np.max(np.abs(got - want)))
        tol = 2e-4 * max(1.0, float(np.max(np.abs(want))))
        rows.append({
            "stencil": spec.name, "L": eng.L,
            "max_err": round(err, 8), "allclose": bool(err <= tol),
        })
    return rows


def tuned_stencil_sweep(points, n, iters, seed=1):
    """End-to-end: default direct engine vs the tuner's measured plan."""
    from repro.core.stencil import make_stencil
    from repro.tuner import PlanCache, plan_for
    from repro.tuner.plan import Plan
    from repro.tuner.search import measure

    cache = PlanCache()
    rng = np.random.default_rng(seed)
    rows = []
    for shape, ndim, r in points:
        spec = make_stencil(shape, ndim, r, seed=9)
        x = jnp.asarray(rng.normal(size=(n + 2 * r, n + 2 * r)), jnp.float32)
        plan = plan_for(spec, x.shape, x.dtype, cache=cache, iters=iters)
        td = measure(cache.engine(spec, Plan.default(spec)), x, iters=2 * iters)
        tt = measure(cache.engine(spec, plan), x, iters=2 * iters)
        rows.append({
            "stencil": spec.name, "plan": plan.describe(),
            "default_us": round(td * 1e6, 1),
            "tuned_us": round(tt * 1e6, 1),
            "speedup": round(td / tt, 2),
        })
    return rows, cache.stats.as_dict()


#: the stencil registry the fused engine sweep validates against
REGISTRY = (("star", 1), ("box", 1), ("star", 2), ("box", 2))


def run(radii=(1, 2, 3, 5, 7), n=1 << 14, iters=20, tuned_n=256,
        tuned_iters=5, seed=0, out=None, fused_radii=(1, 2, 3),
        fused_n=512, fused_engine_n=24):
    spmm = spmm_sweep(radii, n, iters, seed=seed)
    fused_kernel = fused_kernel_sweep(fused_radii, fused_n)
    fused_engine = fused_engine_sweep(
        tuple((s, d, r) for s, d in REGISTRY for r in fused_radii),
        fused_engine_n)
    tuned, tuner_stats = tuned_stencil_sweep(
        (("star", 2, 1), ("box", 2, 2), ("box", 2, 3)),
        tuned_n, tuned_iters)
    payload = {
        "schema": SCHEMA,
        "version": VERSION,
        "generated_unix": round(time.time(), 1),
        "env": {"backend": jax.default_backend(),
                "device_count": jax.device_count(),
                "python": platform.python_version(),
                "jax": jax.__version__},
        "config": {"radii": list(radii), "n": n, "iters": iters,
                   "tuned_n": tuned_n, "tuned_iters": tuned_iters,
                   "seed": seed},
        "spmm": spmm,
        "fused_kernel": fused_kernel,
        "fused_engine": fused_engine,
        "tuned_vs_default": tuned,
        "tuner": tuner_stats,
    }
    if out:
        with open(out, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=None,
                    help="SpMM columns (default: 16384, 2048 in --quick)")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--quick", action="store_true",
                    help="small CI profile (fewer columns/iters/radii)")
    ap.add_argument("--out", default="BENCH_kernels.json")
    args = ap.parse_args(argv)

    radii = (1, 2, 3) if args.quick else (1, 2, 3, 5, 7)
    n = args.n or (1 << 11 if args.quick else 1 << 14)
    iters = args.iters or (5 if args.quick else 20)
    tuned_n = 64 if args.quick else 256
    payload = run(radii=radii, n=n, iters=iters, tuned_n=tuned_n,
                  tuned_iters=3 if args.quick else 5, out=args.out,
                  fused_radii=(1, 2) if args.quick else (1, 2, 3),
                  fused_n=256 if args.quick else 512,
                  fused_engine_n=16 if args.quick else 24)

    print("# kernel microbench: dense padded GEMM vs compressed 2:4 SpMM")
    print("radius,L,n,dense_us,sptc_us,dense_gmacs,sptc_gmacs")
    for row in payload["spmm"]:
        print(f"{row['radius']},{row['L']},{row['n']},{row['dense_us']},"
              f"{row['sptc_us']},{row['dense_gmacs']},{row['sptc_gmacs']}")
    print("# sptc executes K/2 — per-useful-MAC throughput is the metric")
    print()
    print("# fused pallas_sptc v2 (interpret mode) vs direct oracle")
    print("radius,L,general_ok,star_fast_ok,bf16_ok,interp_cpu_us")
    for row in payload["fused_kernel"]:
        print(f"{row['radius']},{row['L']},{row['general_ok']},"
              f"{row['star_fast_ok']},{row['bf16_ok']},"
              f"{row['interp_cpu_us']}")
    print("# interp wall clock is CPU Python")
    print()
    print("# fused engine (registry x radius): pallas_sptc vs direct")
    for row in payload["fused_engine"]:
        print(f"{row['stencil']},L{row['L']},allclose={row['allclose']},"
              f"err={row['max_err']}")
    print()
    print("# end-to-end stencil: default direct engine vs repro.tuner plan")
    print("stencil,plan,default_us,tuned_us,speedup")
    for row in payload["tuned_vs_default"]:
        print(f"{row['stencil']},{row['plan']},{row['default_us']},"
              f"{row['tuned_us']},{row['speedup']}x")
    print(f"# tuner cache: {payload['tuner']}")
    if args.out:
        print(f"# artifact written to {args.out}")
    return payload


if __name__ == "__main__":
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache()
    main()
