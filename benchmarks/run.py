"""Benchmark aggregator: one section per paper table/figure + the roofline
table from the dry-run results.

    PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import time


def _section(title):
    print(f"\n{'='*72}\n== {title}\n{'='*72}", flush=True)


def main() -> None:
    t0 = time.time()
    from benchmarks import (fig10_scaling, kernel_bench, roofline_table,
                            serving_bench, table1_costs)
    _section("Table 1 — analytic cost model (paper §2.3/§3.2.3)")
    table1_costs.main()
    _section("Figure 10 — throughput vs problem size")
    fig10_scaling.main([])
    _section("Kernel microbench — dense GEMM vs 2:4 SpMM")
    kernel_bench.main()
    _section("Serving driver — continuous batching (BENCH_serving.json)")
    serving_bench.main([], out="BENCH_serving.json", quick=True)
    _section("Roofline table — dry-run derived (EXPERIMENTS.md §Roofline)")
    roofline_table.main([])
    print(f"\n# benchmarks completed in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache()
    main()
