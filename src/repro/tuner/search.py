"""Autotuner: candidate enumeration, timing, and a static cost model.

Two selection modes:

  ``time``  build each candidate engine, run warmup (absorbing the jit
            compile — SPIDER's "slight compile-time cost"), then take the
            median of ``iters`` wall-clock runs.  Ground truth, used by
            benchmarks and long-lived serving processes.
  ``cost``  rank candidates by a static per-output-point model in the
            spirit of ``core/analysis.py`` (Table 1): MACs charged at the
            executing unit's relative throughput plus a per-dispatch
            overhead.  Deterministic and build-free — used when timing is
            disabled (tests, cold imports, sizing dry-runs).

Candidates are the applicable backends (``kernels.dispatch``) crossed
with a small even-``L`` grid (paper §3.2.2 fixes L = 2r+2 for exact 50%
band density; larger L trades density for fewer, bigger GEMM tiles) and,
for 2-D non-star stencils on the matrix backends, the fused-rows variant.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stencil import StencilSpec
from repro.core.transform import decompose_rows, default_l
from repro.tuner.plan import Plan

# Cost-model constants (relative, dimensionless). The matrix units (MXU /
# SpTC) retire MACs ~an order of magnitude faster than scalar/vector FMA;
# every separate 1-D application (gather + dispatch) carries fixed overhead.
MATRIX_UNIT_SPEEDUP = 8.0
DISPATCH_OVERHEAD = 0.25


def l_candidates(radius: int, max_candidates: int = 3) -> List[int]:
    """Small even-L grid: the paper's 2r+2 plus MXU-friendlier roundings."""
    base = default_l(radius)
    cands = {base, -(-base // 8) * 8}
    if 16 >= base:
        cands.add(16)
    return sorted(cands)[:max_candidates]


def candidate_plans(spec: StencilSpec, device: str | None = None, *,
                    temporal_steps: int = 1,
                    variable_coefficients: bool = False) -> List[Plan]:
    """All plans worth trying for ``spec`` on ``device``.

    ``temporal_steps`` stamps every candidate with the requested temporal
    block; ``variable_coefficients`` restricts to the backends/modes the
    variable-coefficient emitter supports (jnp backends, no row fusion,
    no temporal blocking — see ``transform.lower_spec``).
    """
    from repro.kernels.dispatch import applicable_backends
    plans: List[Plan] = []
    star = spec.shape == "star"
    k = temporal_steps
    for backend in applicable_backends(spec, device):
        if variable_coefficients and backend not in ("direct", "gemm",
                                                     "sptc"):
            continue
        if backend in ("direct", "pallas_direct"):
            plans.append(Plan(backend=backend, L=default_l(spec.radius),
                              temporal_steps=k))
            continue
        for L in l_candidates(spec.radius):
            plans.append(Plan(backend=backend, L=L, temporal_steps=k))
            if (spec.ndim == 2 and not star and backend in ("gemm", "sptc")
                    and not variable_coefficients):
                plans.append(Plan(backend=backend, L=L, fuse_rows=True,
                                  temporal_steps=k))
    return plans


def _n_applications(spec: StencilSpec, plan: Plan) -> int:
    if spec.ndim == 1:
        return 1
    if plan.star_fast_path and spec.shape == "star":
        return spec.ndim
    return len(decompose_rows(spec))


def static_cost(spec: StencilSpec, plan: Plan) -> float:
    """Relative cost per output point (lower is better).

    direct      taps MACs on the scalar/vector unit, one dispatch per tap.
    gemm-like   2L MACs per point per 1-D application (dense band, §2.3's
                >=2x waste) on the matrix unit.
    sptc-like   L MACs per point per application (SpTC executes K/2, §3.2.3)
                on the matrix unit.
    fuse_rows   same MACs, one dispatch (§Perf D single stacked GEMM).
    temporal    a k-step block costs k× one step (per-step work is
                unchanged — the §3.3 zero-overhead profile holds per step).
    """
    napps = _n_applications(spec, plan)
    if plan.backend == "direct":
        macs, tput, dispatches = float(spec.taps), 1.0, spec.taps
    elif plan.backend == "pallas_direct":
        # same MACs as direct, fused into one kernel with in-VMEM reuse
        macs, tput, dispatches = float(spec.taps), 2.0, 1
    elif plan.backend in ("gemm", "pallas_mxu"):
        macs, tput, dispatches = float(napps * 2 * plan.L), MATRIX_UNIT_SPEEDUP, napps
    elif plan.backend in ("sptc", "pallas_sptc"):
        macs, tput, dispatches = float(napps * plan.L), MATRIX_UNIT_SPEEDUP, napps
    else:
        raise ValueError(f"unknown backend {plan.backend}")
    if plan.fuse_rows:
        dispatches = 1
    return plan.temporal_steps * (macs / tput
                                  + DISPATCH_OVERHEAD * dispatches)


@dataclasses.dataclass(frozen=True)
class Candidate:
    plan: Plan
    score: float               # seconds (time mode) or model cost (cost mode)


@dataclasses.dataclass(frozen=True)
class TuneResult:
    plan: Plan
    mode: str
    candidates: Tuple[Candidate, ...]

    @property
    def best_score(self) -> float:
        return min(c.score for c in self.candidates if c.plan == self.plan)


def _default_engine_factory(spec: StencilSpec, plan: Plan,
                            coefficients: Any = None) -> "StencilEngine":
    from repro.core.engine import StencilEngine
    return StencilEngine(spec, backend=plan.backend, L=plan.L,
                         star_fast_path=plan.star_fast_path,
                         fuse_rows=plan.fuse_rows,
                         temporal_steps=plan.temporal_steps,
                         coefficients=coefficients)


def measure(fn: Callable, x: jnp.ndarray, warmup: int = 1,
            iters: int = 3) -> float:
    """Median wall-clock seconds per call; warmup absorbs the jit compile."""
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn(x))
    ts = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def autotune(spec: StencilSpec, shape: Sequence[int],
             dtype: Any = jnp.float32, *,
             mode: str = "time",
             engine_factory: Callable | None = None,
             temporal_steps: int = 1, coefficients: Any = None,
             warmup: int = 1, iters: int = 3, seed: int = 0) -> TuneResult:
    """Pick the best Plan for (spec, input shape, dtype) on this device.

    ``shape`` is the halo-inclusive input shape, exactly what the engine
    will be called with (for a k-step temporal block that means the k·r
    halo; for variable coefficients it must match the field's fixed
    shape).  ``mode == "cost"`` ranks by the static cost model.  In
    ``"time"`` mode a candidate that fails to build, compile or run raises
    ``RuntimeError`` naming its plan: every applicable backend must work
    on this device, so a failure is a fault, not a slower plan.
    """
    if mode not in ("time", "cost"):
        raise ValueError(f"mode must be 'time' or 'cost', got {mode!r}")
    plans = candidate_plans(spec, temporal_steps=temporal_steps,
                            variable_coefficients=coefficients is not None)
    if not plans:
        raise RuntimeError(f"no applicable backends for {spec.name}")
    factory = engine_factory or _default_engine_factory

    if mode == "cost":
        cands = tuple(Candidate(p, static_cost(spec, p)) for p in plans)
        best = min(cands, key=lambda c: c.score)
        return TuneResult(plan=best.plan, mode="cost", candidates=cands)

    x = jnp.asarray(np.random.default_rng(seed).normal(size=tuple(shape)),
                    dtype=dtype)
    cands: List[Candidate] = []
    for p in plans:
        try:
            eng = factory(spec, p, coefficients=coefficients)
            t = measure(eng, x, warmup=warmup, iters=iters)
        except Exception as e:
            raise RuntimeError(
                f"autotune candidate {p} for {spec.name} on input "
                f"{tuple(shape)} failed to build, compile or run") from e
        cands.append(Candidate(p, t))
    best = min(cands, key=lambda c: c.score)
    return TuneResult(plan=best.plan, mode="time", candidates=tuple(cands))
