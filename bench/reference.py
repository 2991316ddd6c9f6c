"""Plain reference: a straightforward shifted-sum stencil in jax.numpy.

It imports nothing of the program and takes its weights from the
configuration file.  Output point ``(i, j)`` of one step is
``sum_{a, b} w[a, b] * u[i + a, j + b]`` over the halo-inclusive input
``u``; ``iterate`` re-pads the result with a zero halo after every step
(the zero Dirichlet boundary of the configurations).

``precision`` is how every product and sum of a step is rounded:

* ``"float32"``: the reference;
* ``"high"``: each product as XLA's three-pass bfloat16 algorithm forms
  it (``w_hi*x_hi + w_hi*x_lo + w_lo*x_hi``, the ``lo*lo`` term dropped),
  sums in float32: the control for a configuration whose contractions run
  at ``HIGHEST``;
* ``"bfloat16"``: every operation rounded to bfloat16: the control for
  plain float32 arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


PRECISIONS = ("float32", "high", "bfloat16")


def _bf16(v):
    """Round float32 values to bfloat16 explicitly, so no compiler may keep
    float32's excess precision between operations (XLA may, for a bare
    bfloat16 op chain)."""
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


def _product(wk: float, x: jnp.ndarray, precision: str) -> jnp.ndarray:
    w = jnp.asarray(wk, jnp.float32)
    if precision == "float32":
        return w * x
    if precision == "bfloat16":
        return _bf16(_bf16(w) * _bf16(x))
    w_hi, x_hi = _bf16(w), _bf16(x)
    w_lo, x_lo = _bf16(w - w_hi), _bf16(x - x_hi)
    return w_hi * x_hi + (w_hi * x_lo + w_lo * x_hi)


def step(u: jnp.ndarray, weights: np.ndarray, precision: str = "float32") -> jnp.ndarray:
    """One application to a halo-inclusive ``(N + 2r, M + 2r)`` grid;
    float32 out."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
    w = np.asarray(weights, dtype=np.float64)
    r = (w.shape[0] - 1) // 2
    n, m = u.shape[-2] - 2 * r, u.shape[-1] - 2 * r
    rnd = _bf16 if precision == "bfloat16" else (lambda v: v)
    u = u.astype(jnp.float32)
    acc = jnp.zeros(u.shape[:-2] + (n, m), jnp.float32)
    for a in range(2 * r + 1):
        for b in range(2 * r + 1):
            if w[a, b] != 0.0:
                acc = rnd(acc + _product(w[a, b], u[..., a:a + n, b:b + m], precision))
    return acc


def _key(weights) -> tuple:
    w = np.asarray(weights, dtype=np.float64)
    return w.shape, tuple(w.ravel().tolist())


def _pad(y: jnp.ndarray, r: int) -> jnp.ndarray:
    return jnp.pad(y, [(0, 0)] * (y.ndim - 2) + [(r, r), (r, r)])


@functools.lru_cache(maxsize=None)
def _iterate_fn(wkey: tuple, steps: int, precision: str, sharding):
    w = np.asarray(wkey[1], dtype=np.float64).reshape(wkey[0])
    r = (w.shape[0] - 1) // 2

    def body(u, _):
        return step(_pad(u, r), w, precision), None

    def run(u):
        return jax.lax.scan(body, u.astype(jnp.float32), None, length=steps)[0]

    kw = {} if sharding is None else {"in_shardings": sharding,
                                      "out_shardings": sharding}
    return jax.jit(run, **kw)


def iterate(u: jnp.ndarray, weights: np.ndarray, steps: int,
            precision: str = "float32", sharding=None) -> jnp.ndarray:
    """``steps`` steps of an interior grid with a zero boundary; float32 out.

    Each step pads the interior with a zero halo and applies ``step``.
    With ``sharding`` the grid stays partitioned over the devices it names
    and XLA's partitioner exchanges the halos (no code of the program).
    """
    return _iterate_fn(_key(weights), int(steps), precision, sharding)(u)


def max_rel_err(got: jnp.ndarray, want: jnp.ndarray) -> float:
    """max |got - want| over max |want|: the widest gap, in units of the
    state's own scale (the state shrinks under smoothing, so an absolute
    gap would mean different things at different step counts)."""
    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    gap = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    if not np.isfinite(gap):
        return float("inf")
    return gap / scale if scale > 0 else gap
