"""Share of device busy time in ops that are neither Mosaic kernels nor
collectives, over all chips of the cell.

Kernels and collectives are told apart by the compiled step program's
text (``bench/trace.py``).  What is left is emit's data movement: axis
moves and transposes around the kernels, slices, row-op sums, the re-pad
between steps and the halo concatenations.  Layer: emit.
"""


def read(facts):
    red = facts.get("trace")
    if red is None:
        return None
    busy = sum(d.busy_s for d in red.devices.values())
    other = sum(d.other_s for d in red.devices.values())
    return 100.0 * other / busy if busy > 0 else None
