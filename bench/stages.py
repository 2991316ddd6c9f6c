#!/usr/bin/env python3
"""Split one cell's off-kernel device time by program stage, on the chip.

    python3 bench/stages.py --workload <cell> --seed <n> [--seconds <s>]

From the root of a checkout.  Sets the cell up as ``bench/run.py`` does,
profiles one window of the cell's ``trace_seconds`` (or ``--seconds``) and
prints one JSON line:

* ``gstencil_per_s``: the traced window's rate (calls x steps x points
  over the window), to set against the untraced runs' for the profiler's
  own cost;
* ``offkernel_busy_frac``: ``offkernel_busy_frac.iterate`` read from this
  trace by the benchmark's own reader;
* ``stages``: each stage's share (%) of device busy time
  (``bench/scopes.py``), ``unscoped`` included, and ``stages_sum``;
* ``unscoped_ops``: the unscoped instructions and their seconds per chip.

Nothing is compared with the reference here: ``bench/run.py`` does that.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def measure(name: str, wl: dict, config: dict, seed: int, seconds: float) -> dict:
    """Set the cell up, profile one window of ``seconds``, split it."""
    import jax
    from bench import harness, run, scopes, trace
    spec, weights = run.program_spec(config)
    ctx = run.Context(name, seed, wl, config, spec, weights)
    entry = harness.load_entry(wl["entry"]).Entry(ctx)
    entry.setup()
    trace_dir = tempfile.mkdtemp(prefix="bench-stages-")
    try:
        jax.profiler.start_trace(trace_dir)
        with harness.span(trace.WINDOW_SPAN):
            entry.window(seconds)
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
        device_events, spans = trace.load(path)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    device_events = {d: e for d, e in device_events.items() if d < len(entry.devices)}
    window = trace.window_of(spans)
    texts = entry.hlo_texts
    kernels, collectives = trace.classify_hlo(texts)
    red = trace.reduce_events(device_events, spans, kernels, collectives, window)
    op_s = scopes.op_seconds(device_events, window)
    shares = scopes.stage_shares(op_s, red.busy_s_mean, texts)
    _, unscoped = scopes.stage_seconds(op_s, texts)
    offkernel = harness.load_reader("offkernel_busy_frac.iterate").read({"trace": red})
    out = {"workload": name, "seed": seed, "window_s": red.window_s,
           "busy_s": red.busy_s_mean, "calls": entry.calls,
           "gstencil_per_s": entry.e2e()["gstencil_per_s"],
           "offkernel_busy_frac": offkernel, "stages": shares,
           "stages_sum": sum(shares.values()),
           "unscoped_ops": dict(sorted(unscoped.items(), key=lambda kv: -kv[1])[:10]),
           "device": {"kind": entry.devices[0].device_kind, "count": len(entry.devices)}}
    entry.close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)

    import jax
    from bench import harness, run
    from repro.compile_cache import use_checkout_cache
    bm = harness.load_benchmark()
    cell = harness.find_workload(bm, args.workload)
    wl = harness.load_workload_file(args.workload)
    config = harness.load_config(bm, cell["config"])
    devices, why = run.preflight(cell)
    if devices is None:
        print(f"stages: {why}; refusing to run", file=sys.stderr)
        return 3
    use_checkout_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seconds = args.seconds if args.seconds is not None else float(wl["trace_seconds"])
    out = measure(args.workload, wl, config, args.seed, seconds)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
