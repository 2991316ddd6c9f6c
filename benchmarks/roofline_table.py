"""Roofline table: render results/dryrun_*.jsonl as the per-(arch x cell x
mesh) three-term table for EXPERIMENTS.md §Roofline, and emit the same rows
as a **versioned JSON artifact** (``BENCH_roofline.json``) mirroring
``kernel_bench.py``'s ``BENCH_kernels.json`` so CI archives the roofline
verdicts alongside the measured benchmarks::

    PYTHONPATH=src python benchmarks/roofline_table.py --out BENCH_roofline.json
    PYTHONPATH=src python benchmarks/roofline_table.py --quick   # CI profile

``--quick`` reads only the newest results file (CI keeps the artifact small
and current); with no results present the artifact still gets written, with
an empty table, so artifact consumers never 404.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import platform
import time
from typing import List, Optional

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")

SCHEMA = "repro/bench_roofline"
VERSION = 1


def result_paths(newest_only: bool = False) -> List[str]:
    try:
        names = sorted(f for f in os.listdir(RESULTS)
                       if f.startswith("dryrun") and f.endswith(".jsonl"))
    except FileNotFoundError:
        return []
    if newest_only and names:
        names = names[-1:]
    return [os.path.join(RESULTS, n) for n in names]


def load(paths: Optional[List[str]] = None,
         newest_only: bool = False) -> List[dict]:
    rows = []
    paths = paths if paths else result_paths(newest_only)
    for p in paths:
        with open(p) as f:
            for line in f:
                rows.append(json.loads(line))
    return rows


def summarize(rows: List[dict]) -> dict:
    ok = [r for r in rows if r.get("ok") and not r.get("skipped")
          and "t_compute_s" in r]
    return {
        "cells": len(rows),
        "compiled": len(ok),
        "skipped": sum(1 for r in rows if r.get("skipped")),
        "failed": sum(1 for r in rows
                      if not r.get("ok") and not r.get("skipped")),
        "bottlenecks": dict(collections.Counter(
            r["bottleneck"] for r in ok)),
    }


def payload(rows: List[dict], sources: List[str]) -> dict:
    return {
        "schema": SCHEMA,
        "version": VERSION,
        "generated_unix": round(time.time(), 1),
        "env": {"python": platform.python_version(),
                "platform": platform.platform()},
        "sources": [os.path.basename(p) for p in sources],
        "summary": summarize(rows),
        "rows": rows,
    }


def fmt_row(r: dict) -> str:
    if r.get("skipped"):
        return (f"| {r['arch']} | {r['cell']} | {r['mesh']} | — | — | — | "
                f"skipped ({r['skipped'][:40]}…) | — | — |")
    if not r.get("ok"):
        return (f"| {r['arch']} | {r['cell']} | {r['mesh']} | — | — | — | "
                f"FAILED: {r.get('error', '?')[:50]} | — | — |")
    return ("| {arch} | {cell} | {mesh} | {tc:.4f} | {tm:.4f} | {tl:.4f} | "
            "{bn} | {uf:.2f} | {mfu:.3f} |").format(
        arch=r["arch"], cell=r["cell"], mesh=r["mesh"],
        tc=r["t_compute_s"], tm=r["t_memory_s"], tl=r["t_collective_s"],
        bn=r["bottleneck"], uf=r.get("useful_frac", 0),
        mfu=r.get("mfu_at_roofline", 0))


def render(rows: List[dict]) -> None:
    if not rows:
        print("# no dry-run results yet — run repro.launch.dryrun first")
        return
    print("| arch | cell | mesh | t_compute s | t_memory s | t_coll s | "
          "bottleneck | useful | MFU@roof |")
    print("|---|---|---|---|---|---|---|---|---|")
    for r in sorted(rows, key=lambda r: (r["arch"], r["cell"],
                                         r.get("mesh", ""))):
        print(fmt_row(r))
    s = summarize(rows)
    if s["compiled"]:
        print(f"\n# {s['compiled']} compiled cells; bottleneck "
              f"distribution: {s['bottlenecks']}")


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("paths", nargs="*",
                    help="explicit results/*.jsonl files (default: all)")
    ap.add_argument("--quick", action="store_true",
                    help="CI profile: newest results file only")
    ap.add_argument("--out", default=None,
                    help="also write the versioned JSON artifact here "
                         "(e.g. BENCH_roofline.json)")
    args = ap.parse_args(argv)
    sources = args.paths or result_paths(newest_only=args.quick)
    rows = load(sources)
    render(rows)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload(rows, sources), f, indent=1, sort_keys=True)
            f.write("\n")


if __name__ == "__main__":
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache()
    main()
