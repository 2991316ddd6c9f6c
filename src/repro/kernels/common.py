"""Shared kernel utilities."""
from __future__ import annotations

import os
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.scopes import KERNEL_PREP

#: env override for interpret-mode resolution: "1" forces interpret=True
#: everywhere (correctness sweeps on any backend), "0" forces compiled
#: Mosaic lowering (only meaningful on a real TPU).
INTERPRET_ENV_VAR = "REPRO_PALLAS_INTERPRET"


def default_interpret() -> bool:
    """Interpret Pallas kernels unless running on a real TPU.

    This container is CPU-only; TPU v5e is the *target*. interpret=True
    executes the kernel body in Python for bit-level validation against the
    ref.py oracles; on TPU the same pallas_call lowers to Mosaic.  The
    ``REPRO_PALLAS_INTERPRET`` env var overrides the device-based default
    in either direction (read at call resolution time, not import time).
    """
    env = os.environ.get(INTERPRET_ENV_VAR, "")
    if env:
        return env not in ("0", "false", "False")
    return jax.default_backend() != "tpu"


def dot_precision(dtype) -> "jax.lax.Precision | None":
    """Contraction precision for a kernel dot over ``dtype`` operands.

    TPU's default f32 matmul is a single bf16 pass, so f32 operands ask for
    ``HIGHEST`` (full f32); bf16 arithmetic happens only where a caller
    chose a bf16 compute dtype explicitly.
    """
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def fold_vmap(call: Callable) -> Callable:
    """Make ``vmap`` of a manual-DMA kernel run on the kernel's batch axis.

    ``call(x, *consts)`` takes ``x`` of shape (nb, rows, cols) and returns
    (nb, rows', cols').  The returned ``f(x, *consts)`` takes ``x`` with
    any number of leading axes.  Pallas' own batching rule cannot batch an
    input kept in HBM (``pl.ANY``) for Mosaic, so every ``vmap`` over ``x``
    folds into ``call``'s leading axis instead; ``consts`` stay unbatched.
    """
    @jax.custom_batching.custom_vmap
    def f(x, *consts):
        lead = x.shape[:-2]
        with jax.named_scope(KERNEL_PREP):
            x = x.reshape((-1,) + x.shape[-2:])
        y = call(x, *consts)
        with jax.named_scope(KERNEL_PREP):
            return y.reshape(lead + y.shape[-2:])

    @f.def_vmap
    def _rule(axis_size, in_batched, x, *consts):
        if any(in_batched[1:]):
            raise NotImplementedError(
                "only the kernel's input array may be vmapped")
        if not in_batched[0]:
            x = jnp.broadcast_to(x, (axis_size,) + x.shape)
        return f(x, *consts), True

    return f


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# TPU v5e hardware tiling constants (target hardware).
LANES = 128          # minor-most dim of a VREG / MXU edge
SUBLANES = 8         # second-minor dim of a VREG (fp32)
MXU = 128            # systolic array edge
