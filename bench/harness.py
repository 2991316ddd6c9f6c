"""What every cell shares: discovery by name, the compile listener, and the
result line.

Nothing here knows a cell, a configuration or a metric: each is a file
found by the name ``BENCHMARK.json`` gives it.

* ``configs/<config>.json`` (the path is the config's ``file``),
* ``workloads/<cell>.json``: ``entry`` (a module ``entries/<entry>.py``),
  the traffic, and the limits of the correctness check,
* ``metrics/<metric>.py``: ``read(facts) -> float | None`` for a per-layer
  metric.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bm['workloads']]}")


def load_config(bm: dict, name: str, root: Path = ROOT) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_workload_file(name: str, bench: Path = BENCH) -> dict:
    return json.loads((bench / "workloads" / f"{name}.json").read_text())


def _load_module(path: Path, modname: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_entry(kind: str) -> ModuleType:
    return importlib.import_module(f"bench.entries.{kind}")


def load_reader(metric: str, bench: Path = BENCH) -> ModuleType:
    safe = metric.replace(".", "_").replace("-", "_")
    return _load_module(bench / "metrics" / f"{metric}.py", f"bench_metric_{safe}")


def end_to_end_for(bm: dict, cell: str) -> List[dict]:
    """End-to-end metrics this cell reports."""
    return [m for m in bm["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_for(bm: dict, cell: str) -> List[dict]:
    """Per-layer metrics this cell reports: those listing it, and those
    without a list whose ``moves`` metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(bm, cell)}
    out = []
    for m in bm["per_layer"]:
        cells = m.get("workloads")
        if (cells is not None and cell in cells) or (cells is None and m["moves"] in e2e):
            out.append(m)
    return out


class Compiles:
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

    def snapshot(self) -> Tuple[int, float, int]:
        return self.compiles, self.compile_s, self.cache_hits

    def since(self, snap: Tuple[int, float, int]) -> dict:
        c, s, h = snap
        return {"compiles": self.compiles - c, "compile_s": self.compile_s - s,
                "cache_hits": self.cache_hits - h}


def peak_hbm(devices) -> Optional[int]:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (free when no trace is on)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def judge(checks: List[Tuple[str, float, float]]) -> bool:
    """Every number compared is finite and at most its limit."""
    return bool(checks) and all(math.isfinite(v) and v <= lim for _, v, lim in checks)


def result_line(correct: bool, attempted: int, failed: int, metrics: Dict[str, dict],
                device: dict, checks: List[Tuple[str, float, float]],
                breakdown: Optional[dict] = None) -> str:
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return json.dumps(out)
