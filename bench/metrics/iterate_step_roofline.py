"""Share of the step's roofline: steps x t_min over device busy time.

t_min (``bench/work.py``) is the least time one step of this grid could
take on one chip: one read and one write of the chip's share of the grid
at peak HBM bandwidth, or two operations per tap per point at peak
compute, whichever is larger.  It depends on the spec and the grid alone,
so the share reads the same work whatever implements the step.  On a mesh
each chip is judged on its own share and busy time; the lowest chip is
reported.  Layer: kernels (the step program on the device).
"""


def read(facts):
    red = facts.get("trace")
    if red is None or not facts.get("steps"):
        return None
    shares = [100.0 * facts["steps"] * facts["t_min_step_s"] / d.busy_s
              for d in red.devices.values() if d.busy_s > 0]
    return min(shares) if shares else None
