#!/usr/bin/env python3
"""Readings that a 3-D cell's correctness limit is set from.

    python3 bench/control3d.py --workload <cell> --seconds <s> \
        --program-seeds 1 2 3 --control-seeds 4 5 6

``bench/control.py``, with the control put in the program's place by the
3-D entry itself (``Entry.control_step``): the plain 3-D reference
(``bench/reference3d.py``) one step below the configuration's precision.
Options and output lines are ``bench/control.py``'s.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import control  # noqa: E402


def control_step(entry, precision: str) -> None:
    entry.control_step(precision)


if __name__ == "__main__":
    control.control_step = control_step
    sys.exit(control.main())
