"""Three-term roofline from a compiled dry-run artifact (no hardware).

  compute    = HLO_FLOPs   / (chips x peak_FLOP/s)
  memory     = HLO_bytes   / (chips x HBM_bw)
  collective = coll_bytes  / (chips x link_bw)

Sources: ``compiled.cost_analysis()`` for FLOPs / bytes-accessed;
collective bytes are NOT in cost_analysis, so we parse the *optimized,
partitioned* HLO (``compiled.as_text()``) and sum operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
The partitioned module is the per-device program, so parsed byte counts are
per-chip; cost_analysis of that module is likewise per-chip — both are
converted to the global quantities the formulas above expect.

Hardware constants: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Tuple

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# shape tokens like f32[128,512] or bf16[8,128]{1,0}
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
# an HLO instruction line: "%name = <shape(s)> opcode(...)" — opcode may be
# prefixed (e.g. all-reduce-start) for async collectives.
_INSTR_RE = re.compile(
    r"=\s*(?:\(?)((?:[a-z0-9]+\[[0-9,]*\][^)]*?,?\s*)+)\)?\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-op byte totals (per-device program).

    Counts the RESULT shape bytes of each collective instruction (== operand
    bytes for all-reduce / permute / all-to-all; for all-gather the result
    is the gathered tensor, for reduce-scatter the operand is the
    pre-scatter tensor — we count the LARGER side, the wire-dominant one).
    """
    out: Dict[str, int] = {op: 0 for op in COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = None
        for op in COLLECTIVES:
            if f" {op}(" in line or f" {op}-start(" in line:
                m = op
                break
        if m is None:
            continue
        # result type(s): between '=' and the opcode token
        eq = line.find("=")
        op_pos = line.find(f" {m}")
        if eq < 0 or op_pos < eq:
            continue
        result_part = line[eq + 1:op_pos]
        nbytes = _shape_bytes(result_part)
        if m == "reduce-scatter":
            # operand (pre-scatter) dominates the wire; parse operand shapes
            operand_part = line[op_pos:]
            ob = _shape_bytes(operand_part)
            nbytes = max(nbytes, ob)
        out[m] += nbytes
    return out


@dataclasses.dataclass(frozen=True)
class Roofline:
    arch: str
    cell: str
    mesh: str
    chips: int
    # global quantities
    flops: float                 # HLO FLOPs (all chips)
    hbm_bytes: float             # HLO bytes accessed (all chips)
    coll_bytes: float            # collective bytes (all chips)
    coll_by_op: Dict[str, int]   # per-device, by op
    # analytic
    model_flops: float           # 6 * N(_active) * D
    # memory footprint
    per_device_bytes: int
    # raw cost_analysis reference (per-device, scan bodies counted once)
    raw_flops: float = 0.0
    raw_bytes: float = 0.0
    top_collectives: tuple = ()

    @property
    def t_compute(self) -> float:
        return self.flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / (self.chips * LINK_BW)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_frac(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — how much compiled compute is useful."""
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline step time."""
        t = self.step_time
        return (self.model_flops / t) / (self.chips * PEAK_FLOPS) if t else 0.0

    def row(self) -> Dict:
        return {
            "arch": self.arch, "cell": self.cell, "mesh": self.mesh,
            "chips": self.chips,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "hlo_gflops": self.flops / 1e9,
            "model_gflops": self.model_flops / 1e9,
            "useful_frac": self.useful_flops_frac,
            "mfu_at_roofline": self.mfu,
            "per_device_gb": self.per_device_bytes / 1e9,
            "coll_by_op_mb": {k: v / 1e6 for k, v in self.coll_by_op.items()
                              if v},
            "raw_gflops_perdev": self.raw_flops / 1e9,
            "top_collectives": list(self.top_collectives[:6]),
        }


def from_compiled(compiled, *, arch: str, cell: str, mesh_name: str,
                  chips: int, model_flops: float) -> Roofline:
    """Build the roofline record from a compiled (partitioned) executable.

    FLOPs / bytes / collective bytes come from the scan-aware HLO analyzer
    (roofline/hlo_parse.py): ``cost_analysis()`` counts while bodies ONCE,
    ignoring the scan-over-layers trip count, so it is kept only as the raw
    reference (``raw_*``).
    """
    from repro.roofline.hlo_parse import analyze
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    raw_flops = float(ca.get("flops", 0.0))
    raw_bytes = float(ca.get("bytes accessed", 0.0))
    tot = analyze(compiled.as_text())
    mem = compiled.memory_analysis()
    per_device_footprint = int(
        getattr(mem, "argument_size_in_bytes", 0)
        + getattr(mem, "output_size_in_bytes", 0)
        + getattr(mem, "temp_size_in_bytes", 0)
        - getattr(mem, "alias_size_in_bytes", 0))
    return Roofline(
        arch=arch, cell=cell, mesh=mesh_name, chips=chips,
        flops=tot.flops * chips,
        hbm_bytes=tot.dot_bytes * chips,
        coll_bytes=tot.coll_total * chips,
        coll_by_op={k: int(v) for k, v in tot.coll_bytes.items()},
        model_flops=model_flops,
        per_device_bytes=per_device_footprint,
        raw_flops=raw_flops, raw_bytes=raw_bytes,
        top_collectives=tuple(t[1] for t in tot.top_collectives),
    )


# ---------------------------------------------------------------------------
# MODEL_FLOPS (6ND) helpers
# ---------------------------------------------------------------------------

def count_active_params(cfg, params_shapes) -> Tuple[int, int]:
    """(total, active) param counts from a ShapeDtypeStruct tree.

    Active discounts MoE experts to top_k/n_experts of expert weights and
    excludes the embedding table (standard 6ND convention counts only
    FLOP-bearing matmul params; the unembed projection IS counted).
    """
    import jax
    import numpy as np
    total = 0
    active = 0
    flat = jax.tree_util.tree_flatten_with_path(params_shapes)[0]
    for path, leaf in flat:
        n = int(np.prod(leaf.shape))
        total += n
        keys = "/".join(str(getattr(p, "key", p)) for p in path)
        if "embed" in keys and "pos" not in keys and not getattr(
                cfg, "tie_embeddings", False):
            # untied input embedding: a gather, not a matmul
            if keys.endswith("embed"):
                continue
        if "/moe/w" in keys or "/moe/router" in keys:
            if "/moe/w" in keys and cfg.n_experts:
                n = n * cfg.top_k // cfg.n_experts
        active += n
    return total, active


def model_flops_for_cell(cfg, cell, params_shapes) -> float:
    """6 * N_active * D for train; 2 * N_active * D for inference cells."""
    _, active = count_active_params(cfg, params_shapes)
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * active * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * active * tokens
    tokens = cell.global_batch * 1          # one decode token
    return 2.0 * active * tokens
