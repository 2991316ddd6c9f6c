"""Tuner benchmark: tuned-vs-default speedup and plan-cache hit rates.

For every stencil in the paper suite (§4.1) at a given problem size:
tune (timing mode by default), report the chosen plan, the default
``direct``-backend time, the tuned time, and the speedup; then replay
every stencil to demonstrate warm-cache behavior (plan hits, zero new
engine builds).  Optionally persists plans to a JSON file so a second
run of this script tunes nothing at all.

    PYTHONPATH=src python benchmarks/tuner_bench.py --size 512
    PYTHONPATH=src python benchmarks/tuner_bench.py --cost-model   # no timing
    PYTHONPATH=src python benchmarks/tuner_bench.py --cache-file /tmp/plans.json
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from repro.core.stencil import paper_suite
from repro.tuner import PlanCache, plan_for, tuned_apply
from repro.tuner.plan import Plan
from repro.tuner.search import measure


def _input(spec, size, rng):
    side3 = max(8, round(size ** (2 / 3)))      # about size^2 points in 3-D
    dims = {1: (size * size,), 2: (size, size), 3: (side3,) * 3}[spec.ndim]
    shape = tuple(s + 2 * spec.radius for s in dims)
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--size", type=int, default=512,
                    help="2-D edge length (1-D problems use size^2 points)")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--cost-model", action="store_true",
                    help="select plans with the static cost model (no timing)")
    ap.add_argument("--cache-file", default=None,
                    help="JSON plan persistence path (survives restarts)")
    args = ap.parse_args()

    mode = "cost" if args.cost_model else "time"
    cache = PlanCache(path=args.cache_file)
    preloaded = len(cache)
    if preloaded:
        print(f"# loaded {preloaded} persisted plans from {args.cache_file}")
    rng = np.random.default_rng(0)

    print("stencil,plan,default_us,tuned_us,speedup")
    for spec in paper_suite():
        x = _input(spec, args.size, rng)
        plan = plan_for(spec, x.shape, x.dtype, cache=cache, mode=mode,
                        iters=args.iters)
        tuned_eng = cache.engine(spec, plan)
        default_eng = cache.engine(spec, Plan.default(spec))
        td = measure(default_eng, x, iters=args.iters)
        tt = measure(tuned_eng, x, iters=args.iters)
        print(f"{spec.name},{plan.describe()},{td*1e6:.1f},{tt*1e6:.1f},"
              f"{td/tt:.2f}x")

    builds_before = cache.stats.engine_builds
    for spec in paper_suite():            # warm replay: plan + engine hits only
        tuned_apply(spec, _input(spec, args.size, rng), cache=cache)
    assert cache.stats.engine_builds == builds_before, "warm replay re-built!"
    s = cache.stats
    print(f"# warm replay: {len(list(paper_suite()))} applies, "
          f"0 new engine builds")
    print(f"# cache stats: plans={len(cache)} hit_rate={s.plan_hit_rate:.2f} "
          f"tunes={s.tunes} engine_builds={s.engine_builds} "
          f"engine_hits={s.engine_hits}")
    if args.cache_file:
        print(f"# plans persisted to {args.cache_file} — rerun to skip tuning")


if __name__ == "__main__":
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache()
    main()
