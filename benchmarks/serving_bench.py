"""Serving-driver benchmark: continuous-batching throughput + artifact.

Drives a randomized mix of stencil jobs (several specs × jittered
shapes × dtypes) through `repro.serving.StencilDriver` and records the
numbers the ROADMAP's perf trajectory needs as a **versioned JSON
artifact** (``BENCH_serving.json``): job throughput, batch occupancy,
padding efficiency, p50/p99 latency, tuned-vs-default speedup per spec,
and tuner plan-cache hit rates.  Every job's result is verified against
the per-job ``tuned_apply`` oracle before the artifact is written.

    PYTHONPATH=src python benchmarks/serving_bench.py --out BENCH_serving.json
    PYTHONPATH=src python benchmarks/serving_bench.py --quick   # CI profile
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stencil import make_stencil
from repro.serving import BatchPolicy, StencilDriver
from repro.tuner import PlanCache, plan_for, tuned_apply
from repro.tuner.plan import Plan
from repro.tuner.search import measure

SCHEMA = "repro/bench_serving"
VERSION = 1


def _specs():
    return [make_stencil("star", 2, 1, seed=1),
            make_stencil("box", 2, 2, seed=2),
            make_stencil("box", 1, 1, seed=3)]


def _job_mix(specs, n_jobs, base, rng):
    """Randomized (spec, halo-inclusive array) jobs; shapes jitter inside
    one pow2 bucket per spec so plan groups see near-miss co-batching."""
    jobs = []
    for i in range(n_jobs):
        spec = specs[i % len(specs)]
        if spec.ndim == 2:
            dims = (int(rng.integers(base // 2 + 1, base + 1)),
                    int(rng.integers(base // 2 + 1, base + 1)))
        else:
            n = base * base
            dims = (int(rng.integers(n // 2 + 1, n + 1)),)
        shape = tuple(s + 2 * spec.radius for s in dims)
        jobs.append((spec, jnp.asarray(rng.normal(size=shape), jnp.float32)))
    return jobs


def _speedups(specs, cache, base, rng, iters):
    """Tuned-engine vs default(direct)-engine time per spec at full size."""
    out = {}
    for spec in specs:
        dims = ((base, base) if spec.ndim == 2 else (base * base,))
        shape = tuple(s + 2 * spec.radius for s in dims)
        x = jnp.asarray(rng.normal(size=shape), jnp.float32)
        tuned_apply(spec, x, cache=cache)      # ensure a plan exists
        plan = plan_for(spec, x.shape, x.dtype, cache=cache)
        td = measure(cache.engine(spec, Plan.default(spec)), x, iters=iters)
        tt = measure(cache.engine(spec, plan), x, iters=iters)
        out[spec.name] = {"plan": plan.describe(),
                          "default_us": round(td * 1e6, 1),
                          "tuned_us": round(tt * 1e6, 1),
                          "speedup": round(td / tt, 3)}
    return out


def run(n_jobs=120, base=48, max_batch=16, max_wait_ms=5.0, mode="cost",
        padding="bucket", iters=5, seed=0, verify=True, out=None):
    rng = np.random.default_rng(seed)
    specs = _specs()
    cache = PlanCache()
    jobs = _job_mix(specs, n_jobs, base, rng)

    # warm pass: one job per plan group so the timed wave measures the
    # steady state (tuning + compiles happen here, not in-flight)
    with StencilDriver(cache=cache, mode=mode, padding=padding) as warm:
        seen = {}
        for spec, x in jobs:
            seen.setdefault(warm.group_key(spec, x), (spec, x))
        warm.map(seen.values())

    driver = StencilDriver(
        cache=cache, mode=mode, padding=padding,
        policy=BatchPolicy(max_batch=max_batch, max_wait_ms=max_wait_ms,
                           max_queue=max(1024, 2 * n_jobs)),
        autostart=False)
    t0 = time.monotonic()
    futures = [driver.submit(spec, x) for spec, x in jobs]
    driver.start()
    results = [f.result() for f in futures]
    wall = time.monotonic() - t0
    metrics = driver.metrics()
    driver.close()

    verified = None
    if verify:
        for (spec, x), y in zip(jobs, results):
            want = tuned_apply(spec, x, cache=cache)
            np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)
        verified = True

    points = sum(int(np.prod(x.shape)) for _, x in jobs)
    payload = {
        "schema": SCHEMA,
        "version": VERSION,
        "generated_unix": round(time.time(), 1),
        "env": {"backend": jax.default_backend(),
                "device_count": jax.device_count(),
                "python": platform.python_version(),
                "jax": jax.__version__},
        "config": {"n_jobs": n_jobs, "base_size": base,
                   "n_specs": len(specs), "max_batch": max_batch,
                   "max_wait_ms": max_wait_ms, "mode": mode,
                   "padding": padding, "seed": seed},
        "throughput": {"wall_s": round(wall, 4),
                       "jobs_per_s": round(n_jobs / wall, 2),
                       "points_per_s": round(points / wall, 1)},
        "batch_occupancy": metrics["overall"]["batch_occupancy"],
        "latency_ms": {"p50": metrics["overall"]["latency"]["p50_ms"],
                       "p99": metrics["overall"]["latency"]["p99_ms"]},
        "per_plan": metrics["plans"],
        "tuner": metrics["tuner"],
        "speedup_vs_default": _speedups(specs, cache, base, rng, iters),
        "verified_against_tuned_apply": verified,
    }
    if out:
        with open(out, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
    return payload


def main(argv=None, out="BENCH_serving.json", quick=False):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--jobs", type=int, default=None)
    ap.add_argument("--size", type=int, default=None,
                    help="2-D edge length ceiling (1-D uses size^2 points)")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--mode", choices=("time", "cost"), default=None,
                    help="plan selection (default: cost in --quick, else time)")
    ap.add_argument("--padding", choices=("bucket", "max", "exact"),
                    default="bucket")
    ap.add_argument("--quick", action="store_true",
                    help="small CI profile (fewer jobs, cost-model plans)")
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument("--out", default=out)
    args = ap.parse_args(argv)

    quick = quick or args.quick
    n_jobs = args.jobs or (40 if quick else 120)
    base = args.size or (32 if quick else 96)
    mode = args.mode or ("cost" if quick else "time")
    payload = run(n_jobs=n_jobs, base=base, max_batch=args.max_batch,
                  max_wait_ms=args.max_wait_ms, mode=mode,
                  padding=args.padding, iters=3 if quick else 5,
                  verify=not args.no_verify, out=args.out)

    th, lat = payload["throughput"], payload["latency_ms"]
    print(f"jobs={n_jobs} specs={payload['config']['n_specs']} "
          f"mode={mode} padding={args.padding}")
    print(f"throughput: {th['jobs_per_s']} jobs/s "
          f"({th['points_per_s']:.3g} points/s) in {th['wall_s']}s")
    print(f"occupancy={payload['batch_occupancy']} "
          f"p50={lat['p50']}ms p99={lat['p99']}ms "
          f"plan_hit_rate={payload['tuner']['plan_hit_rate']}")
    for name, s in payload["speedup_vs_default"].items():
        print(f"  {name:12s} {s['plan']:14s} tuned {s['tuned_us']}us vs "
              f"default {s['default_us']}us -> {s['speedup']}x")
    if payload["verified_against_tuned_apply"]:
        print("all driver outputs verified against per-job tuned_apply")
    if args.out:
        print(f"# artifact written to {args.out}")
    return payload


if __name__ == "__main__":
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache()
    main()
