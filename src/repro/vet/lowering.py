"""Lowered-HLO purity: certify the zero-runtime-overhead claim statically.

SPIDER's §3.3 contract: the on-the-fly input row swap folds into load
addressing, so the *lowered* sparse hot path must contain no more
gather/permute/copy work than the dense path — the only gather allowed
is the intrinsic im2col window read both paths share.  This analyzer
``jax.jit(...).lower(...).compile()``s the stencil engines on abstract
probe shapes (dry-run; no kernel executes on real data), parses the
optimized HLO with :mod:`repro.roofline.hlo_parse`, and walks the
backward operand closure of every ``dot``:

  lowering-dot-count      #dots != expected (one per 1-D application,
                          one total for the fused-rows engine)
  lowering-hot-gather     gathers feeding the matmul exceed the
                          per-application budget (1 = the window read)
  lowering-hot-overhead   dynamic-slice/dynamic-update-slice in the hot
                          path (runtime-indexed addressing — the op the
                          strided swap exists to avoid)
  lowering-sparse-parity  the sptc path lowers with MORE
                          gather/transpose/copy/dynamic-slice ops than
                          the dense gemm path — runtime overhead the
                          paper claims is zero
  lowering-retrace        a fixed-shape engine traces more than once
                          across repeated calls (retracing hazard)

Temporal-blocked probes scale every budget linearly in the block size k:
a k-step engine must lower with exactly k dots per 1-D application and
one window gather per step — the §3.3 zero-overhead profile holds *per
step*, nothing amortizes into extra runtime addressing work.

The **fused-Pallas analyzer** (``analyze_pallas_fused``) certifies the
same contract for the fused ``pallas_sptc`` kernel, which cannot go
through the optimized-HLO walker (interpret-mode pallas_call bodies are
opaque to it).  It counts primitives in the engine's *jaxpr*, without
descending into pallas_call bodies — what remains is exactly the work
performed OUTSIDE the fused program:

  pallas-fused-program    #pallas_call != one fused program per 1-D
                          application
  pallas-fused-gather     gathers outside the fused program exceed the
                          budget (≤ 1 per application; the shipped kernel
                          achieves 0 — the window DMA lives inside)
  pallas-fused-overhead   dynamic-slice/scatter outside the program, or
                          more transpose/gather ops than the dense
                          pallas_mxu engine lowers with — a standalone
                          permute that failed to fold into the kernel

The **sharded analyzer** (``analyze_sharded``) certifies the distributed
halo-exchange hot path (``distributed/halo.py``) when this process sees
more than one device:

  sharded-collective-budget  a fused k-step lowers with != 2
                          collective-permutes per partitioned mesh axis
                          (low + high edge; zero-flux boundary is free)
  sharded-all-gather      anything gather-shaped (all-gather, all-reduce,
                          all-to-all) on the sharded hot path — the
                          partitioner rematerialized the global grid

``verdict()`` additionally returns the per-backend op counts (keyed by
kernel name: ``stencil_gemm``, ``sptc_spmm``, ``sptc_spmm_fused``) that
the CLI emits as the certified zero-overhead status.
"""
from __future__ import annotations

import inspect
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import StencilEngine
from repro.core.stencil import StencilSpec, make_stencil
from repro.core.transform import decompose_rows
from repro.roofline import hlo_parse
from repro.vet.config import VetConfig
from repro.vet.findings import Finding

_PATH = "src/repro/core/engine.py"

#: engine backend -> the kernel subsystem its lowering certifies
BACKEND_KERNEL = {"gemm": "stencil_gemm", "sptc": "sptc_spmm"}

#: opcodes whose presence in the hot path is runtime overhead to account
OVERHEAD_OPS = ("gather", "transpose", "copy", "dynamic-slice",
                "dynamic-update-slice")

#: (spec ctor args, fuse_rows, temporal steps, probe input shape) —
#: small, compile-fast; the k=2 probe certifies the per-step profile
PROBES: Tuple[Tuple[Tuple[str, int, int], bool, int, Tuple[int, ...]],
              ...] = (
    (("star", 2, 1), False, 1, (34, 34)),
    (("box", 2, 1), True, 1, (34, 34)),
    (("star", 2, 1), False, 2, (36, 36)),
)


def _finding(cfg: VetConfig, rule: str, symbol: str, message: str) -> Finding:
    return Finding(rule=rule, severity=cfg.severity_of(rule), path=_PATH,
                   line=0, symbol=symbol, message=message)


def n_applications(spec: StencilSpec, fused: bool) -> int:
    """1-D applications the engine performs (== expected dot count)."""
    if fused:
        return 1
    if spec.ndim == 1:
        return 1
    if spec.shape == "star":
        return spec.ndim
    return len(decompose_rows(spec))


def lower_engine(engine: StencilEngine,
                 shape: Tuple[int, ...]) -> hlo_parse.HotPathReport:
    """Optimized-HLO hot-path report for one engine at one probe shape."""
    fn = inspect.unwrap(engine._fn)
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    text = jax.jit(fn).lower(x).compile().as_text()
    return hlo_parse.hot_path(text)


def hot_counts(report: hlo_parse.HotPathReport) -> Dict[str, int]:
    hist = report.histogram()
    counts = {op: hist.get(op, 0) for op in OVERHEAD_OPS}
    counts["dot"] = len(report.dots)
    return counts


def trace_count(engine: StencilEngine, shape: Tuple[int, ...],
                calls: int = 3) -> int:
    """How many times the engine function traces across same-shape calls."""
    fn = inspect.unwrap(engine._fn)
    n = [0]

    def counting(x):
        n[0] += 1
        return fn(x)

    jitted = jax.jit(counting)
    rng = np.random.default_rng(0)
    for _ in range(max(1, calls)):
        x = jnp.asarray(rng.normal(size=shape), jnp.float32)
        jax.block_until_ready(jitted(x))
    return n[0]


def analyze_backend(cfg: VetConfig, backend: str
                    ) -> Tuple[List[Finding], Dict[str, dict]]:
    """Findings + per-probe op counts for one engine backend."""
    findings: List[Finding] = []
    per_probe: Dict[str, dict] = {}
    kernel = BACKEND_KERNEL.get(backend, backend)
    budget = cfg.lowering_budgets.get(backend, {})
    for (shape_kind, ndim, radius), fused, steps, probe_shape in PROBES:
        spec = make_stencil(shape_kind, ndim, radius, seed=7)
        symbol = (f"{kernel}/{spec.name}{'/fused' if fused else ''}"
                  f"{f'/k{steps}' if steps != 1 else ''}")
        engine = StencilEngine(spec, backend=backend, fuse_rows=fused,
                               temporal_steps=steps)
        report = lower_engine(engine, probe_shape)
        counts = hot_counts(report)
        per_probe[symbol] = counts
        # every budget scales linearly in the temporal block size: the
        # zero-overhead profile must hold per step (§3.3)
        napps = n_applications(spec, fused) * steps
        if counts["dot"] != napps:
            findings.append(_finding(
                cfg, "lowering-dot-count", symbol,
                f"expected {napps} dot(s) (one per 1-D application per "
                f"step), lowered program has {counts['dot']}"))
        gather_budget = budget.get("gather", 1) * napps
        if counts["gather"] > gather_budget:
            findings.append(_finding(
                cfg, "lowering-hot-gather", symbol,
                f"{counts['gather']} gather(s) feed the matmul hot path "
                f"(budget {gather_budget}: the im2col window read only) — "
                "a row swap or metadata gather failed to fold into load "
                "addressing (§3.3)"))
        dyn = counts["dynamic-slice"] + counts["dynamic-update-slice"]
        dyn_budget = budget.get("dynamic-slice", 0) * napps
        if dyn > dyn_budget:
            findings.append(_finding(
                cfg, "lowering-hot-overhead", symbol,
                f"{dyn} dynamic-slice op(s) feed the matmul hot path "
                f"(budget {dyn_budget}) — runtime-indexed addressing in a "
                "statically-known access pattern"))
    return findings, per_probe


# ---------------------------------------------------------------------------
# Fused-Pallas kernel: jaxpr-level certification (interpret-mode safe —
# tracing only, the kernel never executes here)
# ---------------------------------------------------------------------------

#: ops that, OUTSIDE the fused program, constitute runtime overhead
_JAXPR_OVERHEAD = ("gather", "transpose", "dynamic_slice",
                   "dynamic_update_slice", "scatter")

#: (spec ctor args, probe input shape) — star exercises the metadata-free
#: fast path, box the faithful one-hot decompression path
PALLAS_PROBES: Tuple[Tuple[Tuple[str, int, int], Tuple[int, ...]], ...] = (
    (("star", 2, 1), (22, 22)),
    (("box", 2, 1), (22, 22)),
)


def _subjaxprs(val):
    vals = val if isinstance(val, (tuple, list)) else (val,)
    for v in vals:
        if hasattr(v, "jaxpr"):            # ClosedJaxpr
            yield v.jaxpr
        elif hasattr(v, "eqns"):           # Jaxpr
            yield v


def _walk_jaxpr(jaxpr, counts: Dict[str, int]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
        if name == "pallas_call":
            continue                       # the fused program is the budget
        for param in eqn.params.values():
            for sub in _subjaxprs(param):
                _walk_jaxpr(sub, counts)


def jaxpr_counts(engine: StencilEngine,
                 shape: Tuple[int, ...]) -> Dict[str, int]:
    """Primitive histogram of the engine's jaxpr, pallas bodies excluded."""
    fn = inspect.unwrap(engine._fn)
    closed = jax.make_jaxpr(fn)(jnp.zeros(shape, jnp.float32))
    counts: Dict[str, int] = {}
    _walk_jaxpr(closed.jaxpr, counts)
    return counts


def analyze_pallas_fused(cfg: VetConfig
                         ) -> Tuple[List[Finding], Dict[str, dict]]:
    """Certify the fused pallas_sptc kernel's zero-overhead profile."""
    findings: List[Finding] = []
    per_probe: Dict[str, dict] = {}
    budget = cfg.lowering_budgets.get("pallas_sptc", {})
    for (shape_kind, ndim, radius), probe_shape in PALLAS_PROBES:
        spec = make_stencil(shape_kind, ndim, radius, seed=7)
        symbol = f"sptc_spmm_fused/{spec.name}"
        engine = StencilEngine(spec, backend="pallas_sptc")
        counts = jaxpr_counts(engine, probe_shape)
        dense = jaxpr_counts(StencilEngine(spec, backend="pallas_mxu"),
                             probe_shape)
        keep = dict.fromkeys(_JAXPR_OVERHEAD, 0)
        keep.update({k: v for k, v in counts.items()
                     if k in _JAXPR_OVERHEAD or k == "pallas_call"})
        keep.setdefault("pallas_call", 0)
        per_probe[symbol] = keep
        napps = n_applications(spec, fused=False)
        if keep["pallas_call"] != napps:
            findings.append(_finding(
                cfg, "pallas-fused-program", symbol,
                f"expected {napps} fused pallas program(s) (one per 1-D "
                f"application), traced {keep['pallas_call']}"))
        gather_budget = budget.get("gather", 1) * napps
        if keep["gather"] > gather_budget:
            findings.append(_finding(
                cfg, "pallas-fused-gather", symbol,
                f"{keep['gather']} gather(s) outside the fused program "
                f"(budget {gather_budget}) — windowing/swap/metadata work "
                "failed to fold into the kernel (§3.3)"))
        dyn = (keep["dynamic_slice"] + keep["dynamic_update_slice"]
               + keep["scatter"])
        if dyn > budget.get("dynamic-slice", 0) * napps:
            findings.append(_finding(
                cfg, "pallas-fused-overhead", symbol,
                f"{dyn} dynamic-slice/scatter op(s) outside the fused "
                "program — runtime-indexed addressing in a statically-"
                "known access pattern"))
        for op in ("gather", "transpose"):
            if keep[op] > dense.get(op, 0):
                findings.append(_finding(
                    cfg, "pallas-fused-overhead", symbol,
                    f"{keep[op]} {op} op(s) outside the fused program vs "
                    f"the dense pallas_mxu engine's {dense.get(op, 0)} — a "
                    "standalone permute the paper's row swap eliminates"))
    return findings, per_probe


# ---------------------------------------------------------------------------
# Sharded halo exchange: collective budget on the distributed hot path
# ---------------------------------------------------------------------------

_SHARDED_PATH = "src/repro/distributed/halo.py"

#: opcodes that would mean the partitioner fell back to gathering the
#: whole grid instead of exchanging width-k·r halos
_GATHER_LIKE = ("all-gather", "all-to-all", "all-reduce", "reduce-scatter")


def collective_counts(text: str) -> Dict[str, int]:
    """Collective-permutes and gather-shaped collectives in compiled HLO."""
    hist = hlo_parse.opcode_histogram(hlo_parse.parse_module(text))
    permutes = (hist.get("collective-permute", 0)
                + hist.get("collective-permute-start", 0))
    gathers = sum(hist.get(op, 0) + hist.get(op + "-start", 0)
                  for op in _GATHER_LIKE)
    return {"collective-permute": permutes, "gather-like": gathers}


def sharded_probes() -> Tuple[Tuple[Tuple[str, int, int], tuple, int,
                                    Tuple[int, ...]], ...]:
    """(spec ctor args, mesh parts, temporal steps, probe interior shape),
    scaled to however many devices this process sees."""
    n = jax.device_count()
    if n < 2:
        return ()
    probes = [
        (("star", 2, 1), (2,), 1, (24, 24)),
        (("box", 2, 1), (2,), 2, (24, 24)),
    ]
    if n >= 4:
        probes.append((("box", 2, 2), (2, 2), 1, (24, 24)))
    return tuple(probes)


def analyze_sharded(cfg: VetConfig
                    ) -> Tuple[List[Finding], Dict[str, dict]]:
    """Certify the halo-exchange collective budget on the step hot path.

    The distributed contract: one fused k-step exchanges exactly TWO
    collective-permutes per partitioned axis (low edge + high edge; the
    zero-flux boundary is ppermute's zero fill, costing nothing extra)
    and nothing gather-shaped — an all-gather in the lowered program
    means the partitioner rematerialized the global grid.  Probes lower
    ``ShardedStencilEngine``'s device-resident step/iterate path (the
    one-time halo-inclusive ``__call__`` boundary reshard is not the
    steady state).  Needs >= 2 devices; returns empty findings and
    probes otherwise (CI supplies virtual devices via
    ``--xla_force_host_platform_device_count``).
    """
    findings: List[Finding] = []
    per_probe: Dict[str, dict] = {}
    from repro.distributed.halo import ShardedStencilEngine, grid_mesh
    for (shape_kind, ndim, radius), parts, steps, shape in sharded_probes():
        spec = make_stencil(shape_kind, ndim, radius, seed=7)
        mesh_tag = "x".join(str(p) for p in parts)
        symbol = (f"halo/{spec.name}/mesh{mesh_tag}"
                  f"{f'/k{steps}' if steps != 1 else ''}")
        engine = ShardedStencilEngine(spec, grid_mesh(parts),
                                      backend="sptc",
                                      temporal_steps=steps)
        naxes = len(engine.partition())
        u = jax.ShapeDtypeStruct(shape, jnp.float32)
        for tag, nblocks in (("step", 1), ("iterate", 2)):
            text = jax.jit(engine._run_sharded, static_argnums=1).lower(
                u, nblocks).compile().as_text()
            counts = collective_counts(text)
            per_probe[f"{symbol}/{tag}"] = counts
            expected = 2 * naxes
            if counts["collective-permute"] != expected:
                findings.append(Finding(
                    rule="sharded-collective-budget",
                    severity=cfg.severity_of("sharded-collective-budget"),
                    path=_SHARDED_PATH, line=0, symbol=f"{symbol}/{tag}",
                    message=(
                        f"expected exactly {expected} collective-permutes "
                        f"per fused step (2 per partitioned axis × {naxes} "
                        f"axes), lowered program has "
                        f"{counts['collective-permute']}")))
            if counts["gather-like"]:
                findings.append(Finding(
                    rule="sharded-all-gather",
                    severity=cfg.severity_of("sharded-all-gather"),
                    path=_SHARDED_PATH, line=0, symbol=f"{symbol}/{tag}",
                    message=(
                        f"{counts['gather-like']} all-gather/all-reduce/"
                        "all-to-all op(s) on the sharded hot path — the "
                        "partitioner rematerialized the global grid "
                        "instead of exchanging width-k·r halos")))
    return findings, per_probe


def run(cfg: VetConfig) -> Tuple[List[Finding], Dict[str, dict]]:
    """All lowering findings + the per-backend zero-overhead verdict."""
    findings: List[Finding] = []
    verdict: Dict[str, dict] = {}
    counts_by_backend: Dict[str, Dict[str, dict]] = {}
    for backend in cfg.lowering_backends:
        fs, per_probe = analyze_backend(cfg, backend)
        findings += fs
        counts_by_backend[backend] = per_probe
        kernel = BACKEND_KERNEL.get(backend, backend)
        verdict[kernel] = {
            "probes": per_probe,
            "certified": not fs,
        }
    # sparse-vs-dense parity: sptc may not out-gather/out-copy gemm
    if "gemm" in counts_by_backend and "sptc" in counts_by_backend:
        dense = counts_by_backend["gemm"]
        sparse = counts_by_backend["sptc"]
        for d_sym, s_sym in zip(sorted(dense), sorted(sparse)):
            for op in OVERHEAD_OPS:
                if sparse[s_sym][op] > dense[d_sym][op]:
                    f = _finding(
                        cfg, "lowering-sparse-parity", s_sym,
                        f"sptc hot path has {sparse[s_sym][op]} {op} op(s) "
                        f"vs gemm's {dense[d_sym][op]} — sparse execution "
                        "added runtime overhead the paper claims is zero")
                    findings.append(f)
                    verdict["sptc_spmm"]["certified"] = False
    # fused Pallas kernel: jaxpr-level zero-overhead certification
    fused_findings, fused_probes = analyze_pallas_fused(cfg)
    findings += fused_findings
    verdict["sptc_spmm_fused"] = {
        "probes": fused_probes,
        "certified": not fused_findings,
    }
    # distributed halo exchange: collective budget per partitioned axis
    # (probes exist only when this process sees >= 2 devices)
    sharded_findings, sharded_probes_ran = analyze_sharded(cfg)
    findings += sharded_findings
    if sharded_probes_ran:
        verdict["sharded_halo"] = {
            "probes": sharded_probes_ran,
            "certified": not sharded_findings,
        }
    # retracing: a fixed-shape engine must trace exactly once
    for backend in cfg.lowering_backends:
        kernel = BACKEND_KERNEL.get(backend, backend)
        spec = make_stencil("star", 2, 1, seed=7)
        engine = StencilEngine(spec, backend=backend)
        traces = trace_count(engine, (34, 34))
        verdict[kernel]["traces"] = traces
        if traces != 1:
            findings.append(_finding(
                cfg, "lowering-retrace", f"{kernel}/{spec.name}",
                f"fixed-shape engine traced {traces} times over 3 "
                "same-shape calls — retracing hazard in the hot path"))
            verdict[kernel]["certified"] = False
    return findings, verdict
