"""An iterate run with its timed path broken underneath comes out not
correct.

Each test skips the harness's look for a chip and drives the rest of a
run on the CPU at a small size (``run_cell``), with one fault planted in
what the window drives: a step that returns its state unchanged, an
answer altered where it is produced, and the control (the plain
reference computed one step below the configuration's precision, put in
the program's place).  A sound run of the same size comes out correct.
The sharded cell's faults are in
``test_bench_faults_mesh.py``.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, reference
from bench.tests.drive import control_hook, run_small

#: one call of 8 steps (a window of 0 s runs one call): on a grid this
#: small the suite's drifting weights carry the state out through the zero
#: boundary within a few thousand steps
ITERATE = {"grid": [48, 48], "steps_per_call": 8}


def _unchanged(entry):
    entry.step = lambda u: u


def _altered(entry):
    step = entry.step
    entry.step = lambda u: step(u).at[entry.r + 5, entry.r + 7].add(1.0)


#: the control's gap grows with the steps taken (the box's ``high`` control
#: reads 7e-6 after 8 steps and 6.6e-5 after 128 on a 128^2 grid); the
#: program is only warmed at this size, so its direct backend stands in
CONTROL = {"grid": [128, 128], "steps_per_call": 128, "backend": "direct"}


@pytest.mark.parametrize("cell", ["heat-2d.iterate", "box-2d49p.iterate"])
@pytest.mark.parametrize("fault,size", [(None, ITERATE), (_unchanged, ITERATE),
                                        (_altered, ITERATE), (control_hook, CONTROL)],
                         ids=["sound", "state_unchanged", "answer_altered", "control"])
def test_iterate_faults(cell, fault, size, cpu_run):
    out = run_small(cell, size, fault)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0


def test_reference_matches_the_shipped_engine(cpu_run):
    """The plain reference agrees with the program's direct and pallas_sptc
    backends at a small size, one step and iterated."""
    from repro.core.engine import StencilEngine
    from repro.core.stencil import paper_suite
    for spec in (s for s in paper_suite() if s.name in ("star-2d1r", "box-2d3r")):
        r = spec.radius
        x = jax.random.normal(jax.random.key(3), (40 + 2 * r, 36 + 2 * r), jnp.float32)
        want = reference.step(x, spec.weights)
        for backend in ("direct", "pallas_sptc"):
            eng = StencilEngine(spec, backend=backend)
            assert reference.max_rel_err(eng(x), want) < 1e-6, (spec.name, backend)
        u = x[r:-r, r:-r]
        got = StencilEngine(spec, backend="pallas_sptc").iterate(jnp.pad(u, r), 6)[r:-r, r:-r]
        assert reference.max_rel_err(got, reference.iterate(u, spec.weights, 6)) < 1e-6
        want = reference.iterate(u, spec.weights, 6)
        for precision, above in (("bfloat16", 1e-4), ("high", 1e-7)):
            low = reference.iterate(u, spec.weights, 6, precision)
            assert reference.max_rel_err(low, want) > above, precision


SUITE_2D = ["star-2d1r", "star-2d2r", "star-2d3r", "box-2d1r", "box-2d2r", "box-2d3r"]


@pytest.mark.parametrize("name", SUITE_2D)
def test_reference_step_is_the_plain_shifted_sum(name, cpu_run):
    """One reference step equals a float64 loop over the taps, written out
    here, for every 2-D spec of the suite."""
    from repro.core.stencil import paper_suite
    w = np.asarray(next(s for s in paper_suite() if s.name == name).weights)
    r = (w.shape[0] - 1) // 2
    x = np.asarray(jax.random.normal(jax.random.key(11), (20 + 2 * r, 17 + 2 * r)))
    want = np.zeros((20, 17))
    for a in range(2 * r + 1):
        for b in range(2 * r + 1):
            want += w[a, b] * x[a:a + 20, b:b + 17]
    got = np.asarray(reference.step(jnp.asarray(x, jnp.float32), w))
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-6


@pytest.mark.parametrize("value,correct", [(4.9e-7, True), (1e-3, True), (0.158, False),
                                           (math.nan, False)])
def test_control_reading_carries_the_judges_verdict(value, correct):
    line = json.loads(control.reading("heat-2d.iterate", 7, "control_bfloat16", 3,
                                      [("max_rel_err", value, 1e-3)]))
    assert line["correct"] is correct
    assert line["checks"]["max_rel_err"]["limit"] == 1e-3
