#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, its traffic and
its per-layer metrics are found by name (``bench/harness.py``).  The run
refuses to start without a TPU, with fewer chips than the cell asks for,
or with Pallas forced into interpret mode; it then exits non-zero and
prints no result.

Set-up (``setup_s``: process start to window start) builds the state from
the seed on the device and warms every program the window runs, through
JAX's persistent compilation cache in ``<checkout>/.jax_cache`` (or
``JAX_COMPILATION_CACHE_DIR``).  ``--trace 0`` measures the window and
reports the end-to-end metrics; ``--trace 1`` profiles a window of the
cell's ``trace_seconds`` and reports the per-layer metrics.  Both then
compare what the window produced with the plain reference
(``bench/reference.py``).

Standard error ends with the set-up accounting and each number compared
beside its limit; the last line of standard output is the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


class Context:
    """What an entry is given: the cell's files, the seed and the spec."""

    def __init__(self, cell: str, seed: int, workload: dict, config: dict,
                 program_spec, weights) -> None:
        self.cell = cell
        self.seed = seed
        self.workload = workload
        self.config = config
        self.program_spec = program_spec
        self.weights = weights


def program_spec(config: dict):
    """The program's suite spec named by the configuration, after checking
    that its weights are the configuration's, bit for bit."""
    import numpy as np
    from repro.core.stencil import paper_suite
    spec = next((s for s in paper_suite() if s.name == config["spec"]), None)
    if spec is None:
        raise KeyError(f"the program's suite has no spec {config['spec']!r}")
    weights = np.asarray(config["weights"], dtype=np.float64)
    if not np.array_equal(np.asarray(spec.weights), weights):
        raise ValueError(f"{config['spec']}: the program's weights differ from "
                         f"the configuration's")
    return spec, weights


def preflight(cell: dict):
    """The devices to run on, or the reason to refuse."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return None, f"no TPU (first device is {devices[0].platform})"
    if len(devices) < cell["chips"]:
        return None, f"the cell asks for {cell['chips']} chips, JAX sees {len(devices)}"
    from repro.kernels import common
    if common.default_interpret():
        return None, f"{common.INTERPRET_ENV_VAR} forces interpret mode"
    from bench import work
    work.peaks(devices[0].device_kind)          # an unknown chip is an error
    return devices, None


def run_cell(bm: dict, name: str, wl: dict, config: dict, seed: int, seconds: float,
             trace: bool, entry_hook=None) -> Tuple[str, List[str]]:
    """Set up, measure, check; returns (result line, closing stderr lines).

    ``entry_hook(entry)``, when given, runs after set-up: the tests use it to
    break the timed path underneath a run.
    """
    import jax
    from bench import harness, trace as tracing
    from repro.compile_cache import use_checkout_cache
    cache_dir = use_checkout_cache()
    # small programs (the seeded initial state, its pad) are cached too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = harness.Compiles()
    jax.monitoring.register_event_listener(compiles.on_event)
    jax.monitoring.register_event_duration_secs_listener(compiles.on_duration)

    spec, weights = program_spec(config)
    ctx = Context(name, seed, wl, config, spec, weights)
    entry = harness.load_entry(wl["entry"]).Entry(ctx)
    entry.setup()
    if entry_hook is not None:
        entry_hook(entry)
    # what set-up made lives for the whole run: keep the collector off it,
    # so its passes in the window scan only what the window allocates
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    in_setup = compiles.since((0, 0.0, 0))
    snap = compiles.snapshot()

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        with harness.span(tracing.WINDOW_SPAN):
            entry.window(min(seconds, float(wl["trace_seconds"])))
        jax.profiler.stop_trace()
    else:
        entry.window(seconds)
    in_window = compiles.since(snap)
    mem_peak = harness.peak_hbm(entry.devices)

    d0 = entry.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(entry.devices), "memory_peak_bytes": mem_peak}
    metrics, breakdown = {}, None
    if trace:
        facts = entry.facts()
        red = tracing.reduce_dir(trace_dir, facts.get("hlo_texts", []),
                                 n_devices=len(entry.devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        facts["trace"] = red
        device["busy_s"] = red.busy_s_mean
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        for m in harness.per_layer_for(bm, name):
            value = harness.load_reader(m["name"]).read(facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(entry.e2e(), setup_s=setup_s)
        for m in harness.end_to_end_for(bm, name):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    attempted, failed = entry.attempted_failed()
    setup_info = dict(entry.setup_facts(), setup_s=setup_s, compile_cache_dir=cache_dir,
                      setup_compiles=in_setup, window_compiles=in_window,
                      memory_peak_bytes=mem_peak)

    checks = entry.check()
    entry.close()
    correct = harness.judge(checks) and failed == 0
    lines = ["bench setup " + json.dumps(setup_info), f"bench correct = {correct}"]
    lines += [f"bench check {n} = {v!r} (limit {lim!r})" for n, v, lim in checks]
    return harness.result_line(correct, attempted, failed, metrics, device, checks,
                               breakdown), lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    bm = harness.load_benchmark()
    cell = harness.find_workload(bm, args.workload)
    wl = harness.load_workload_file(args.workload)
    config = harness.load_config(bm, cell["config"])
    devices, why = preflight(cell)
    if devices is None:
        print(f"bench: {why}; refusing to run", file=sys.stderr)
        return 3
    line, closing = run_cell(bm, args.workload, wl, config, args.seed, args.seconds,
                             bool(args.trace))
    for text in closing:
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
