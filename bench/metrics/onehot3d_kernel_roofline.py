"""Share of the 3-D one-hot kernel's roofline: steps x t_min over the
device seconds of the ``sptc_onehot3d`` kernels.

t_min (``bench/work.py``) is the least time one step of this grid could
take on one chip: one read and one write of the grid at peak HBM
bandwidth, or two operations per tap per point at peak compute, whichever
is larger.  It reads the same work whatever implements the step.  The
kernel's seconds are what the trace's top ops give the kernel
instructions named ``sptc_onehot3d`` (the program's ``pallas_call``
name), per chip.  A program without that kernel reads nothing.
Layer: kernels.
"""

KERNEL = "sptc_onehot3d"


def _is_kernel(key: str) -> bool:
    name, _, kind = key.rpartition(" [")
    return kind == "kernel]" and name.rsplit(".", 1)[0] == KERNEL


def read(facts):
    red = facts.get("trace")
    if red is None or not facts.get("steps"):
        return None
    secs = sum(s for key, s in red.top_ops if _is_kernel(key))
    if secs <= 0:
        return None
    return 100.0 * facts["steps"] * facts["t_min_step_s"] / secs
