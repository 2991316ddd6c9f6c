#!/usr/bin/env python3
"""Chip smoke: drive the stencil engine's main path once on a TPU.

    python chip_smoke.py              # one chip: engine, tuner + serving
    python chip_smoke.py --chips 4    # 2x2 mesh: sharded iterate only

One process, no subprocesses.  It exits non-zero, without printing a
result, unless JAX's first device is a TPU; it never falls back to the CPU
and refuses ``REPRO_PALLAS_INTERPRET`` forcing interpret mode.

Phases (one chip):

* ``engine`` — every 2-D spec of ``PAPER_SUITE`` at the paper's 10240²
  float32 grid: ``StencilEngine(spec, "pallas_sptc").iterate(x, 4)`` against
  the ``direct`` oracle's ``iterate`` on the same seeded input; then one
  ``pallas_direct`` and one ``pallas_mxu`` run at 10240².  Each Pallas
  engine's compiled step must hold a Mosaic kernel (``tpu_custom_call``).
* ``serving`` — ``StencilDriver`` in ``mode="time"`` serves 24 jobs over 3
  paper-suite 2-D specs with interior sides 1025–2000: every TPU candidate
  plan, Pallas included, is built, compiled and timed in process (a failing
  candidate raises), and every result is checked against ``direct``.

``--chips 4`` runs only ``sharded``: ``ShardedStencilEngine`` with
``pallas_sptc`` on a 2x2 mesh iterates a 20480² float32 grid (10240² per
chip), compared with a one-chip ``direct`` iterate of the same grid; its
compiled program must exchange halos with collective-permutes and hold no
all-gather.

Every line before the last is one JSON record naming the device.  The last
line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.compile_cache import use_checkout_cache  # noqa: E402
from repro.core.engine import StencilEngine  # noqa: E402
from repro.core.stencil import paper_suite  # noqa: E402
from repro.distributed.halo import ShardedStencilEngine, grid_mesh  # noqa: E402
from repro.kernels import common  # noqa: E402
from repro.serving import BatchPolicy, StencilDriver  # noqa: E402
from repro.tuner import PlanCache, plan_for  # noqa: E402
from repro.vet.lowering import collective_counts  # noqa: E402

SEED = 0
SIDE = 10240            # the paper's 2-D size (benchmarks/fig9_throughput.py)
STEPS = 4
#: |result - direct| bound for float32 inputs drawn from N(0, 1).  Every
#: paper-suite stencil is a convex smoothing kernel, so values stay O(1);
#: f32 contractions differ from the oracle only in summation order (~1e-6),
#: while one bf16 pass (TPU's default f32 matmul) errs by ~1e-2.
TOL = 1e-4


class _Compiles:
    """Backend compiles and persistent-cache hits seen by this process."""

    def __init__(self) -> None:
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def on_duration(self, event: str, secs: float, **_: object) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _peak_hbm() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _max_err(a, b) -> float:
    return float(jnp.max(jnp.abs(a - b)))


def _check(name: str, err: float) -> None:
    if not np.isfinite(err) or err > TOL:
        raise AssertionError(f"{name}: max |err| {err} exceeds {TOL}")


def _assert_kernel(name: str, compiled_text: str) -> None:
    if "tpu_custom_call" not in compiled_text:
        raise AssertionError(f"{name}: compiled program holds no Mosaic "
                             "kernel (tpu_custom_call)")


def _normal(shape, sharding=None):
    key = jax.random.key(SEED)
    gen = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                  out_shardings=sharding)
    return gen(key)


def phase_engine(kind: str) -> None:
    specs = [s for s in paper_suite() if s.ndim == 2]
    runs = [(s, "pallas_sptc") for s in specs]
    box1 = next(s for s in specs if s.name == "box-2d1r")
    runs += [(box1, "pallas_direct"), (box1, "pallas_mxu")]
    for spec, backend in runs:
        t0 = time.perf_counter()
        x = _normal((SIDE + 2 * spec.radius,) * 2)
        eng = StencilEngine(spec, backend=backend)
        _assert_kernel(f"{spec.name}/{backend}",
                       eng._fn.lower(x).compile().as_text())
        got = eng.iterate(x, steps=STEPS)
        want = StencilEngine(spec, backend="direct").iterate(x, steps=STEPS)
        err = _max_err(got, want)
        del got, want, x
        _emit({"phase": "engine", "spec": spec.name, "backend": backend,
               "grid": [SIDE, SIDE], "steps": STEPS, "max_err": err,
               "tol": TOL, "wall_s": time.perf_counter() - t0,
               "device": kind})
        _check(f"engine {spec.name}/{backend}", err)


def phase_serving(kind: str) -> None:
    t0 = time.perf_counter()
    rng = np.random.default_rng(SEED)
    specs = [s for s in paper_suite()
             if s.name in ("star-2d1r", "box-2d1r", "box-2d3r")]
    jobs = []
    for i in range(24):
        spec = specs[i % len(specs)]
        h, w = (int(v) for v in rng.integers(1025, 2001, size=2))
        x = jax.random.normal(jax.random.key(SEED + 1 + i),
                              (h + 2 * spec.radius, w + 2 * spec.radius),
                              jnp.float32)
        jobs.append((spec, x))
    cache = PlanCache()                 # in process: no plan file is read
    driver = StencilDriver(cache=cache, mode="time", autostart=False,
                           policy=BatchPolicy(max_batch=8, max_wait_ms=50.0))
    try:
        futures = [driver.submit(spec, x) for spec, x in jobs]
        driver.start()
        results = [f.result(timeout=900) for f in futures]
        metrics = driver.metrics()
    finally:
        driver.close()
    oracles = {s.name: StencilEngine(s, backend="direct") for s in specs}
    err = max(_max_err(y, oracles[spec.name](x))
              for (spec, x), y in zip(jobs, results))
    plans = {}
    for spec, x in jobs[:len(specs)]:   # tuned already: plan cache hits
        plan = plan_for(spec, x.shape, x.dtype, cache=cache)
        plans[spec.name] = f"{plan.backend}/L{plan.L}"
    _emit({"phase": "serving", "jobs": len(jobs),
           "tunes": metrics["tuner"]["tunes"],
           "batches": metrics["overall"]["batches"],
           "plans": plans, "max_err": err, "tol": TOL,
           "wall_s": time.perf_counter() - t0, "device": kind})
    _check("serving", err)


def phase_sharded(kind: str) -> None:
    mesh = grid_mesh((2, 2))
    side = 2 * SIDE
    sharding = NamedSharding(mesh, P("sp0", "sp1"))
    for name in ("star-2d1r", "box-2d1r"):
        spec = next(s for s in paper_suite() if s.name == name)
        t0 = time.perf_counter()
        u = _normal((side, side), sharding)
        eng = ShardedStencilEngine(spec, mesh, backend="pallas_sptc")
        text = eng._run.lower(u, STEPS).compile().as_text()
        _assert_kernel(f"sharded {name}", text)
        counts = collective_counts(text)
        if counts["collective-permute"] == 0 or counts["gather-like"]:
            raise AssertionError(
                f"sharded {name}: halo exchange lowered to {counts}; "
                "expected collective-permutes and no all-gather")
        got = eng.iterate(u, steps=STEPS)
        # the oracle runs on chip 0 alone: free what it does not need first
        # (the 20480² box iterate holds ~10 GB of its 16 GB)
        chip0 = jax.devices()[0]
        u0 = jax.device_put(u, chip0)
        del u
        direct = StencilEngine(spec, backend="direct")
        r = spec.radius
        want = jax.jit(lambda v: direct.iterate(jnp.pad(v, r), steps=STEPS)
                       [r:-r, r:-r])(u0)
        del u0
        err = _max_err(jax.device_put(got, chip0), want)
        del got, want
        _emit({"phase": "sharded", "spec": name, "backend": "pallas_sptc",
               "mesh": [2, 2], "grid": [side, side], "steps": STEPS,
               "collectives": counts, "max_err": err, "tol": TOL,
               "wall_s": time.perf_counter() - t0, "device": kind})
        _check(f"sharded {name}", err)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the 2x2-mesh sharded phase")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {devices[0].platform}); "
              "refusing to run", file=sys.stderr)
        return 2
    if common.default_interpret():
        print(f"chip_smoke: {common.INTERPRET_ENV_VAR} forces interpret "
              "mode; refusing to run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but only {len(devices)} "
              "devices", file=sys.stderr)
        return 2
    cache_dir = use_checkout_cache()
    compiles = _Compiles()
    jax.monitoring.register_event_listener(compiles.on_event)
    jax.monitoring.register_event_duration_secs_listener(
        compiles.on_duration)
    kind = devices[0].device_kind

    phases = ([phase_sharded] if args.chips == 4
              else [phase_engine, phase_serving])
    for phase in phases:
        try:
            phase(kind)
        except Exception:
            traceback.print_exc()
            print(f"chip_smoke: phase {phase.__name__} failed",
                  file=sys.stderr)
            return 1
    _emit({"phase": "summary", "compiles": compiles.compiles,
           "compile_s": compiles.compile_s,
           "persistent_cache_hits": compiles.cache_hits,
           "compile_cache_dir": cache_dir, "peak_hbm_bytes": _peak_hbm(),
           "device": kind})
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
