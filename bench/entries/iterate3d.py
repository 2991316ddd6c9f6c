"""Entry ``iterate3d``: a 3-D PDE time-stepper on one chip.

The 3-D ``iterate``: ``StencilEngine(spec, backend).iterate(u,
steps_per_call)`` under one ``jax.jit`` with the state donated, called
back to back on a seeded 3-D state that stays on the device.  The check
moves the program's final interior to the host first, so the plain 3-D
reference (``bench/reference3d.py``) has the chip's memory to itself over
every step the window took, then compares the two on the device.

Workload keys as for ``iterate``; ``grid`` holds the three interior sides
of a cube.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference3d
from bench.entries import iterate

#: the four low sine modes of the initial state, as (p, q, s) wave numbers
MODES = ((1, 1, 1), (2, 1, 1), (1, 3, 1), (2, 2, 2))


def initial_state(seed: int, n: int, init: dict, pad: int = 0, dtype=jnp.float32):
    """Seeded ``n^3`` interior: low sine modes of order 1 plus white noise,
    with a zero halo of ``pad`` made in the same program."""
    modes = MODES[:init["modes"]]

    def gen(key):
        k_amp, k_sign, k_noise = jax.random.split(key, 3)
        amp = 0.5 + jax.random.uniform(k_amp, (len(modes),))
        sign = jnp.where(jax.random.bernoulli(k_sign, 0.5, (len(modes),)), 1.0, -1.0)
        x = (jnp.arange(n, dtype=jnp.float32) + 1.0) / (n + 1.0)
        u = init["noise"] * jax.random.normal(k_noise, (n, n, n), jnp.float32)
        for m, (p, q, s) in enumerate(modes):
            u = u + (sign[m] * amp[m]) * (jnp.sin(np.pi * p * x)[:, None, None]
                                          * jnp.sin(np.pi * q * x)[None, :, None]
                                          * jnp.sin(np.pi * s * x)[None, None, :])
        u = u.astype(dtype)
        return jnp.pad(u, pad) if pad else u

    return jax.jit(gen)(jax.random.key(seed))


class Entry(iterate.Entry):
    """One-chip 3-D iterate."""

    def initial_interior(self):
        return initial_state(self.ctx.seed, self.n, self.wl["init"])

    def initial(self):
        return initial_state(self.ctx.seed, self.n, self.wl["init"], pad=self.r)

    def interior(self, u):
        r = self.r
        return u[r:-r, r:-r, r:-r]

    def points(self) -> int:
        return self.n ** 3

    def setup_facts(self) -> dict:
        return dict(super().setup_facts(), grid=[self.n] * 3)

    def check(self):
        """Final state against the reference over the same steps."""
        got = np.asarray(jax.jit(self.interior)(self.u))
        self.u = None
        want = self.initial_interior()
        for _ in range(self.calls):
            want = reference3d.iterate(want, self.ctx.weights, self.spc)
        err = reference3d.max_rel_err(jnp.asarray(got), want)
        return [("max_rel_err", err, self.wl["limits"]["max_rel_err"])]

    def control_step(self, precision: str) -> None:
        """The reference at ``precision`` in the place of the timed path
        (``bench/control3d.py``)."""
        w, spc, r = self.ctx.weights, self.spc, self.r

        def ref(u):
            return jnp.pad(reference3d.iterate(u[r:-r, r:-r, r:-r], w, spc, precision), r)

        self.step = jax.jit(ref, donate_argnums=0)
