"""Drive a benchmark run on the CPU at a small size, with a hook that may
break the timed path underneath it."""
import json
from pathlib import Path

from bench import control, harness
from bench import run as bench_run

ROOT = Path(__file__).resolve().parents[2]


def _cell(name, small):
    bm = harness.load_benchmark(ROOT)
    cell = harness.find_workload(bm, name)
    wl = harness.load_workload_file(name)
    wl.update(small)
    return bm, wl, harness.load_config(bm, cell["config"], root=ROOT)


def run_small(name, small, hook=None, seconds=0.0):
    bm, wl, cfg = _cell(name, small)
    line, _ = bench_run.run_cell(bm, name, wl, cfg, seed=2**31 + 101, seconds=seconds,
                                 trace=False, entry_hook=hook)
    return json.loads(line)


def control_hook(entry):
    """The control in the program's place (``bench/control.py``)."""
    control.control_step(entry, entry.ctx.config["control_precision"])
