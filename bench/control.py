#!/usr/bin/env python3
"""Readings that the correctness limits are set from, at a cell's own size.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --program-seeds 1 2 3 --control-seeds 4 5 6

One process, one set-up.  For each program seed it runs a window of the
program as a benchmark run does and prints the numbers compared.  For
each control seed it puts the control in the program's place: the plain
reference computed one step below the precision the configuration
states (its ``control_precision``: ``high`` below float32 contractions
at ``HIGHEST``, ``bfloat16`` below plain float32 arithmetic), driven
through the same window and check.  Each line gives the numbers
compared, their limits, and the verdict of the benchmark's own judge
(``harness.judge``): the program has to come out correct and the control
not; the limit lies between the program's largest reading and the
control's smallest.  Run by hand on the chip; the benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def control_step(entry, precision: str) -> None:
    """The reference at ``precision`` in the place of the entry's timed path."""
    import jax.numpy as jnp
    from bench import reference
    w = entry.ctx.weights
    spc, r = entry.spc, entry.r
    if entry.wl["entry"] == "iterate_sharded":
        entry.step = lambda u: reference.iterate(u, w, spc, precision,
                                                 sharding=entry.sharding)
    else:
        entry.step = lambda u: jnp.pad(reference.iterate(u[r:-r, r:-r], w, spc, precision), r)


def reseed(entry, seed: int) -> None:
    """Start the entry's next window from ``seed``'s initial state."""
    entry.ctx.seed = seed
    entry.u = entry.initial()
    entry.calls = 0


def reading(workload: str, seed: int, side: str, calls: int, checks) -> str:
    """One output line: the numbers compared, their limits, the verdict."""
    from bench import harness
    return json.dumps({"workload": workload, "seed": seed, "side": side, "calls": calls,
                       "correct": harness.judge(checks),
                       "checks": {n: {"value": v, "limit": lim} for n, v, lim in checks}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from bench import harness
    from bench import run
    bm = harness.load_benchmark()
    cell = harness.find_workload(bm, args.workload)
    devices, why = run.preflight(cell)
    if devices is None:
        print(f"control: {why}; refusing to run", file=sys.stderr)
        return 3
    import jax
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    wl = harness.load_workload_file(args.workload)
    config = harness.load_config(bm, cell["config"])
    pspec, weights = run.program_spec(config)
    seeds = args.program_seeds + args.control_seeds
    ctx = run.Context(args.workload, seeds[0], wl, config, pspec, weights)
    entry = harness.load_entry(wl["entry"]).Entry(ctx)
    entry.setup()
    program_calls = []
    for i, seed in enumerate(seeds):
        is_control = i >= len(args.program_seeds)
        if is_control and i == len(args.program_seeds):
            control_step(entry, config["control_precision"])
        reseed(entry, seed)
        if is_control and program_calls:
            # as many steps as the program takes in a window
            calls = sorted(program_calls)[len(program_calls) // 2]
            for _ in range(calls):
                entry.u = entry.step(entry.u)
            entry.calls = calls
        else:
            entry.window(args.seconds)
            if not is_control:
                program_calls.append(entry.calls)
        side = ("control_" + config["control_precision"]) if is_control else "program"
        print(reading(args.workload, seed, side, entry.calls, entry.check()), flush=True)
    entry.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
