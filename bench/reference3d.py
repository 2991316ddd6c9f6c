"""Plain 3-D reference: a straightforward shifted-sum stencil in jax.numpy.

The 3-D copy of ``bench/reference.py``.  It imports nothing of the program
and takes its weights from the configuration file.  Output point
``(i, j, k)`` of one step is ``sum_{a, b, c} w[a, b, c] * u[i + a, j + b,
k + c]`` over the halo-inclusive input ``u``; ``iterate`` re-pads the
result with a zero halo after every step (the zero Dirichlet boundary of
the configuration).

``precision`` is how every product and sum of a step is rounded, with
``bench/reference.py``'s rounding helpers:

* ``"float32"``: the reference;
* ``"high"``: each product as XLA's three-pass bfloat16 algorithm forms
  it (``w_hi*x_hi + w_hi*x_lo + w_lo*x_hi``, the ``lo*lo`` term dropped),
  sums in float32: the control for a configuration whose contractions run
  at ``HIGHEST``;
* ``"bfloat16"``: every operation rounded to bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import PRECISIONS, _bf16, _key, _product


def step(u: jnp.ndarray, weights: np.ndarray, precision: str = "float32") -> jnp.ndarray:
    """One application to a halo-inclusive ``(N + 2r, M + 2r, K + 2r)``
    grid; float32 out."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
    w = np.asarray(weights, dtype=np.float64)
    r = (w.shape[0] - 1) // 2
    n, m, k = (s - 2 * r for s in u.shape[-3:])
    rnd = _bf16 if precision == "bfloat16" else (lambda v: v)
    u = u.astype(jnp.float32)
    acc = jnp.zeros(u.shape[:-3] + (n, m, k), jnp.float32)
    for a in range(2 * r + 1):
        for b in range(2 * r + 1):
            for c in range(2 * r + 1):
                if w[a, b, c] != 0.0:
                    x = u[..., a:a + n, b:b + m, c:c + k]
                    acc = rnd(acc + _product(w[a, b, c], x, precision))
    return acc


def _pad(y: jnp.ndarray, r: int) -> jnp.ndarray:
    return jnp.pad(y, [(0, 0)] * (y.ndim - 3) + [(r, r)] * 3)


@functools.lru_cache(maxsize=None)
def _iterate_fn(wkey: tuple, steps: int, precision: str):
    w = np.asarray(wkey[1], dtype=np.float64).reshape(wkey[0])
    r = (w.shape[0] - 1) // 2

    def body(u, _):
        return step(_pad(u, r), w, precision), None

    def run(u):
        return jax.lax.scan(body, u.astype(jnp.float32), None, length=steps)[0]

    return jax.jit(run, donate_argnums=0)


def iterate(u: jnp.ndarray, weights: np.ndarray, steps: int,
            precision: str = "float32") -> jnp.ndarray:
    """``steps`` steps of an interior grid with a zero boundary; float32 out.

    Each step pads the interior with a zero halo and applies ``step``.
    ``u`` is donated: the grid's buffer is reused for the result.
    """
    return _iterate_fn(_key(weights), int(steps), precision)(u)


@jax.jit
def _gap_and_scale(got: jnp.ndarray, want: jnp.ndarray):
    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    return jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want))


def max_rel_err(got: jnp.ndarray, want: jnp.ndarray) -> float:
    """max |got - want| over max |want|: the widest gap, in units of the
    state's own scale.  One fused program: a 1024^3 pair leaves no room on
    one chip for a third grid of differences."""
    gap, scale = map(float, _gap_and_scale(got, want))
    if not np.isfinite(gap):
        return float("inf")
    return gap / scale if scale > 0 else gap
