"""Entry ``iterate``: a PDE time-stepper on one chip.

``StencilEngine(spec, backend).iterate(u, steps_per_call)`` under one
``jax.jit`` with the state donated, called back to back on a seeded
state that stays on the device: each call feeds the next, as a solver
runs.  The window counts whole calls; the check runs the plain reference
over every step the window took, from the same initial state, and compares
the final states.

Workload keys: ``grid`` (interior sides), ``backend``, ``steps_per_call``,
``init`` (``modes``, ``noise``), ``trace_seconds``, ``limits``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, work
from bench.harness import span

#: the four low sine modes of the initial state, as (p, q) wave numbers
MODES = ((1, 1), (2, 1), (1, 3), (2, 2))


def initial_state(seed: int, n: int, init: dict, dtype=jnp.float32, sharding=None):
    """Seeded interior state: low sine modes of order 1 plus white noise.

    Pure smoothing shrinks white noise by orders of magnitude over a
    window of steps; the low modes keep the state of order 1, so a gap in
    the last bits of float32 stays visible at the window's end.
    """
    modes = MODES[:init["modes"]]

    def gen(key):
        k_amp, k_sign, k_noise = jax.random.split(key, 3)
        amp = 0.5 + jax.random.uniform(k_amp, (len(modes),))
        sign = jnp.where(jax.random.bernoulli(k_sign, 0.5, (len(modes),)), 1.0, -1.0)
        x = (jnp.arange(n, dtype=jnp.float32) + 1.0) / (n + 1.0)
        u = init["noise"] * jax.random.normal(k_noise, (n, n), jnp.float32)
        for m, (p, q) in enumerate(modes):
            u = u + (sign[m] * amp[m]) * (jnp.sin(np.pi * p * x)[:, None]
                                          * jnp.sin(np.pi * q * x)[None, :])
        return u.astype(dtype)

    kw = {} if sharding is None else {"out_shardings": sharding}
    return jax.jit(gen, **kw)(jax.random.key(seed))


class Entry:
    """One-chip iterate.  ``ctx`` is the harness's run context."""

    chips_used = 1
    sharding = None

    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.wl = ctx.workload
        self.spec = ctx.program_spec
        self.r = self.spec.radius
        self.n = int(self.wl["grid"][0])
        self.spc = int(self.wl["steps_per_call"])
        self.devices = jax.devices()[:self.chips_used]
        self.calls = 0
        self.elapsed = 0.0

    # -- the program -------------------------------------------------------
    def initial_interior(self):
        return initial_state(self.ctx.seed, self.n, self.wl["init"], sharding=self.sharding)

    def initial(self):
        """The program's state: the interior with its zero halo."""
        return jax.jit(lambda v: jnp.pad(v, self.r))(self.initial_interior())

    def build(self, u):
        from repro.core.engine import StencilEngine
        eng = StencilEngine(self.spec, backend=self.wl["backend"])
        spc = self.spc
        return jax.jit(lambda v: eng.iterate(v, spc), donate_argnums=0
                       ).lower(u).compile()

    def interior(self, u):
        r = self.r
        return u[r:-r, r:-r]

    # -- harness hooks -----------------------------------------------------
    def setup(self) -> None:
        u = self.initial()
        self.step = self.build(u)
        self.hlo_texts = [self.step.as_text()]
        u = self.step(u)                  # first run: loads and warms
        u.block_until_ready()
        del u
        self.u = self.initial()
        self.u.block_until_ready()

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        while True:
            with span("bench.call"):
                self.u = self.step(self.u)
                self.u.block_until_ready()
            self.calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        self.elapsed = time.perf_counter() - t0

    def points(self) -> int:
        return self.n * self.n

    def e2e(self) -> dict:
        steps = self.calls * self.spc
        return {"gstencil_per_s": self.points() * steps / self.elapsed / 1e9}

    def facts(self) -> dict:
        """What the per-layer readers need besides the trace."""
        peak = work.peaks(self.devices[0].device_kind)
        per_chip = self.points() // len(self.devices)
        t_min, _ = work.t_min_step(per_chip, self.spec.taps, 4, peak)
        return {"steps": self.calls * self.spc, "t_min_step_s": t_min,
                "hlo_texts": self.hlo_texts}

    def attempted_failed(self):
        return self.calls, 0

    def setup_facts(self) -> dict:
        return {"steps_per_call": self.spc, "backend": self.wl["backend"],
                "grid": [self.n, self.n]}

    def check(self):
        """Final state against the reference over the same steps."""
        got = self.interior(self.u)
        want = self.initial_interior()
        for _ in range(self.calls):
            want = reference.iterate(want, self.ctx.weights, self.spc,
                                     sharding=self.sharding)
        err = reference.max_rel_err(got, want)
        return [("max_rel_err", err, self.wl["limits"]["max_rel_err"])]

    def close(self) -> None:
        pass
