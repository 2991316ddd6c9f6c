"""Paper Figure 10: scaling — problem size (strong) and device count (weak).

Two sweeps:

  * **strong** (the original figure): single-device SPTC throughput vs
    problem size.  Expectation from the paper: throughput climbs until
    resources saturate, then plateaus (on CPU the same qualitative curve
    appears — dispatch overhead amortizes, then bandwidth saturates).

  * **weak** (`--weak`): fixed per-device grid, increasing device count.
    Each point runs ``ShardedStencilEngine.iterate`` on a 1-D mesh over
    the first n devices with an n·B × W interior — perfect weak scaling
    keeps time/step flat (efficiency = t1/tn → 1.0).  Runnable on CPU
    with virtual devices::

        PYTHONPATH=src python benchmarks/fig10_scaling.py \\
            --weak --devices 8 --out BENCH_scaling.json

    ``--devices N`` sets ``XLA_FLAGS=--xla_force_host_platform_device_``
    ``count=N`` and therefore must act before jax first initializes —
    this module defers every jax import into the sweep functions for
    exactly that reason.  On a real multi-device platform, omit it.

``--out`` writes the versioned ``BENCH_scaling.json`` artifact that CI
uploads per build (see the ``distributed`` job in ci.yml).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Optional

SIZES = (64, 128, 256, 512, 1024, 2048)
QUICK_SIZES = (64, 128, 256)
ARTIFACT_VERSION = 1


def run(iters: int = 5, sizes=SIZES) -> List[dict]:
    """Strong sweep: single-device throughput vs problem size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.engine import StencilEngine
    from repro.core.stencil import make_stencil

    rows = []
    for shape, r in (("box", 2), ("star", 2)):
        spec = make_stencil(shape, 2, r, seed=3)
        eng = StencilEngine(spec, backend="sptc")
        for n in sizes:
            x = jnp.asarray(np.random.default_rng(0).normal(
                size=(n + 2 * r, n + 2 * r)).astype(np.float32))
            y = eng(x)
            jax.block_until_ready(y)
            t0 = time.perf_counter()
            for _ in range(iters):
                y = eng(x)
            jax.block_until_ready(y)
            dt = (time.perf_counter() - t0) / iters
            rows.append({"stencil": spec.name, "n": n,
                         "gstencils": n * n / dt / 1e9})
    return rows


def run_weak(per_device: int = 256, width: int = 256, steps: int = 8,
             iters: int = 3, device_counts=None) -> List[dict]:
    """Weak sweep: fixed per-device block, growing 1-D mesh.

    Grid is (n · per_device) × width over n devices; each measured call
    is ``iterate(u, steps)`` — state device-resident, one halo exchange
    (2 ppermutes) per step.  Reports time per step and weak-scaling
    efficiency t1/tn (1.0 = perfect).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.stencil import make_stencil
    from repro.distributed.halo import ShardedStencilEngine, grid_mesh

    avail = jax.device_count()
    if device_counts is None:
        device_counts = [n for n in (1, 2, 4, 8, 16) if n <= avail]
    rows = []
    for shape, r in (("star", 1), ("box", 1)):
        spec = make_stencil(shape, 2, r, seed=3)
        t1: Optional[float] = None
        for n in device_counts:
            eng = ShardedStencilEngine(spec, grid_mesh((n,)),
                                       backend="sptc")
            u = jnp.asarray(np.random.default_rng(0).normal(
                size=(n * per_device, width)).astype(np.float32))
            y = eng.iterate(u, steps)
            jax.block_until_ready(y)
            t0 = time.perf_counter()
            for _ in range(iters):
                y = eng.iterate(u, steps)
            jax.block_until_ready(y)
            dt = (time.perf_counter() - t0) / iters / steps
            if t1 is None:
                t1 = dt
            rows.append({
                "stencil": spec.name, "devices": n,
                "grid": [n * per_device, width],
                "us_per_step": dt * 1e6,
                "gstencils": n * per_device * width / dt / 1e9,
                "efficiency": t1 / dt if dt > 0 else 0.0,
            })
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--weak", action="store_true",
                    help="run the weak-scaling sweep (needs >1 device "
                         "unless --devices forces virtual ones)")
    ap.add_argument("--strong", action="store_true",
                    help="run the strong (problem-size) sweep; default "
                         "when no sweep flag is given")
    ap.add_argument("--devices", type=int, default=0, metavar="N",
                    help="force N virtual host CPU devices (sets XLA_FLAGS; "
                         "must run before jax initializes)")
    ap.add_argument("--per-device", type=int, default=256,
                    help="weak sweep: interior rows per device")
    ap.add_argument("--steps", type=int, default=8,
                    help="weak sweep: iterate() steps per measured call")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="CI profile: fewer/smaller strong-sweep sizes")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="also write the versioned BENCH_scaling.json")
    args = ap.parse_args(argv)

    if args.devices:
        if "jax" in sys.modules:
            print(f"# --devices {args.devices} ignored: jax is already "
                  "initialized in this process", file=sys.stderr)
        else:
            flag = (f"--xla_force_host_platform_device_count"
                    f"={args.devices}")
            os.environ["XLA_FLAGS"] = " ".join(
                [f for f in (os.environ.get("XLA_FLAGS"), flag) if f])
    do_strong = args.strong or not args.weak
    artifact: dict = {"version": ARTIFACT_VERSION}

    if do_strong:
        print("# Fig 10 — SPTC-backend throughput vs problem size")
        print("stencil,n,gstencils_per_s")
        rows = run(iters=args.iters,
                   sizes=QUICK_SIZES if args.quick else SIZES)
        for row in rows:
            print(f"{row['stencil']},{row['n']},{row['gstencils']:.3f}")
        # qualitative check: large >= small (saturation curve)
        by: dict = {}
        for row in rows:
            by.setdefault(row["stencil"], []).append(row["gstencils"])
        for k, v in by.items():
            print(f"# {k}: small {v[0]:.3f} -> large {v[-1]:.3f} "
                  f"({v[-1]/max(v[0],1e-9):.1f}x scaling gain)")
        artifact["strong"] = rows

    if args.weak:
        import jax
        print(f"# Fig 10b — weak scaling over {jax.device_count()} "
              "device(s), fixed per-device grid")
        print("stencil,devices,us_per_step,gstencils_per_s,efficiency")
        rows = run_weak(per_device=args.per_device, steps=args.steps,
                        iters=args.iters)
        for row in rows:
            print(f"{row['stencil']},{row['devices']},"
                  f"{row['us_per_step']:.1f},{row['gstencils']:.3f},"
                  f"{row['efficiency']:.2f}")
        artifact["weak"] = rows
        artifact["weak_meta"] = {
            "per_device_rows": args.per_device,
            "steps": args.steps,
            "device_count": jax.device_count(),
            "backend": jax.default_backend(),
        }

    if args.out:
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1, sort_keys=True)
        print(f"# wrote {args.out}")


if __name__ == "__main__":
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache()
    main()
