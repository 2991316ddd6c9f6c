"""The 2x2 iterate with the exchange between chips left out comes out not
correct (see ``test_bench_faults.py``); a sound run comes out correct.
Each case runs in a process of its own with four virtual CPU devices.
"""
import json
import os
import subprocess
import sys

import pytest

from bench.tests.drive import ROOT


SHARDED = """
import json, sys
sys.path.insert(0, {root!r}); sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
import repro.compile_cache
repro.compile_cache.use_checkout_cache = lambda: "off"
from bench import harness, run as bench_run
fault = sys.argv[1]
if fault == "no_exchange":
    jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
bm = harness.load_benchmark()
name = "heat-2d.iterate-2x2"
wl = dict(harness.load_workload_file(name), grid=[64, 64], steps_per_call=4)
cfg = harness.load_config(bm, "heat-2d")
line, _ = bench_run.run_cell(bm, name, wl, cfg, seed=2**31 + 5, seconds=0.0, trace=False)
print(line)
"""


@pytest.mark.parametrize("fault", ["sound", "no_exchange"])
def test_sharded_iterate_without_the_exchange_is_not_correct(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARDED.format(root=str(ROOT), src=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code, fault], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is (fault == "sound"), out["checks"]
