"""The 3-D cell: its plain reference, its entry's check, and the reader of
its kernel's roofline share.

The entry is driven on the CPU at a small size (``run_cell``, as
``test_bench_faults.py`` does for the 2-D cells): a sound run comes out
correct, and runs with a fault planted in the timed path come out not
correct.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference3d, trace
from bench.tests.drive import run_small

CELL = "box-3d27p.iterate"
#: one call of 4 steps on a 14^3 interior
SMALL = {"grid": [14, 14, 14], "steps_per_call": 4}


def _weights():
    from repro.core.stencil import paper_suite
    return np.asarray(next(s for s in paper_suite() if s.name == "box-3d1r").weights)


def _loop(w, x):
    """One step as a float64 loop over the taps, written out here."""
    r = (w.shape[0] - 1) // 2
    n = tuple(s - 2 * r for s in x.shape)
    out = np.zeros(n)
    for a in range(2 * r + 1):
        for b in range(2 * r + 1):
            for c in range(2 * r + 1):
                out += w[a, b, c] * x[a:a + n[0], b:b + n[1], c:c + n[2]]
    return out


def test_reference3d_step_is_the_plain_shifted_sum():
    w = _weights()
    x = np.asarray(jax.random.normal(jax.random.key(5), (13, 10, 11)), np.float64)
    got = np.asarray(reference3d.step(jnp.asarray(x, jnp.float32), w))
    want = _loop(w, x)
    assert got.shape == (11, 8, 9)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-6


def test_reference3d_iterate_repads_with_zeros():
    """``iterate`` is the loop's step on the interior re-padded with a zero
    halo, step after step; the lower precisions fall measurably short."""
    w = _weights()
    u = np.asarray(jax.random.normal(jax.random.key(6), (9, 12, 10)), np.float64)
    want = u
    for _ in range(3):
        want = _loop(w, np.pad(want, 1))
    got = np.asarray(reference3d.iterate(jnp.asarray(u, jnp.float32), w, 3))
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-6
    ref = reference3d.iterate(jnp.asarray(u, jnp.float32), w, 3)
    for precision, above in (("bfloat16", 1e-4), ("high", 1e-7)):
        low = reference3d.iterate(jnp.asarray(u, jnp.float32), w, 3, precision)
        assert reference3d.max_rel_err(low, ref) > above, precision


def _dropped_tap(entry):
    """The timed path with one of the 27 taps left out."""
    import dataclasses
    from repro.core.engine import StencilEngine
    w = np.array(entry.spec.weights)
    w[0, 2, 1] = 0.0
    eng = StencilEngine(dataclasses.replace(entry.spec, weights=w),
                        backend=entry.wl["backend"])
    spc = entry.spc
    entry.step = jax.jit(lambda v: eng.iterate(v, spc))


def _one_step_short(entry):
    from repro.core.engine import StencilEngine
    eng = StencilEngine(entry.spec, backend=entry.wl["backend"])
    spc = entry.spc
    entry.step = jax.jit(lambda v: eng.iterate(v, spc - 1))


@pytest.mark.parametrize("fault", [None, _dropped_tap, _one_step_short],
                         ids=["sound", "dropped_tap", "one_step_short"])
def test_iterate3d_faults(fault, cpu_run):
    out = run_small(CELL, SMALL, fault)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] == 1
    assert set(out["metrics"]) == {"gstencil_per_s", "setup_s"}


def _reduction(top_ops):
    dev = trace.DeviceStats(busy_s=2.0, kernel_s=2.0, collective_s=0.0, other_s=0.0,
                            exposed_collective_s=0.0)
    return trace.Reduction(window_s=2.0, devices={0: dev}, top_ops=top_ops, idle_gaps=[])


def test_onehot3d_kernel_roofline_reads_its_kernel_alone():
    from bench import harness
    reader = harness.load_reader("onehot3d_kernel_roofline")
    red = _reduction([("sptc_onehot3d.6 [kernel]", 0.6), ("sptc_onehot3d.7 [kernel]", 0.6),
                      ("sptc_onehot.3 [kernel]", 0.5), ("fusion.1 [op]", 0.3)])
    got = reader.read({"trace": red, "steps": 16, "t_min_step_s": 0.0105})
    assert got == pytest.approx(100.0 * 16 * 0.0105 / 1.2)
    without = _reduction([("sptc_onehot.3 [kernel]", 0.5), ("fusion.1 [op]", 0.3)])
    assert reader.read({"trace": without, "steps": 16, "t_min_step_s": 0.0105}) is None
    assert reader.read({"steps": 16, "t_min_step_s": 0.0105}) is None
