"""The least time a chip could take for one stencil step, from the spec and
the grid alone.

One step of an explicit stencil has to read the grid once and write it
once, and needs two floating-point operations (a multiply and an add) per
tap per point.  Neither number depends on how the program implements the
step, so a share of this bound reads the same work whatever backend, row
decomposition or kernel a later change picks.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an error."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def step_bytes(points: int, itemsize: int) -> float:
    """One read and one write of every grid point."""
    return 2.0 * points * itemsize


def step_flops(points: int, taps: int) -> float:
    """A multiply and an add per tap per point."""
    return 2.0 * taps * points


def t_min_step(points: int, taps: int, itemsize: int, peak: dict) -> tuple:
    """(seconds, bound) of one step over ``points`` points on one chip.

    ``bound`` is ``"hbm"`` or ``"flops"``, whichever of bytes over peak
    bandwidth and operations over peak compute is larger.
    """
    t_mem = step_bytes(points, itemsize) / peak["hbm_bytes_per_s"]
    t_ops = step_flops(points, taps) / peak["bf16_flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "flops")
