"""Share of the traced window in which a collective runs on a chip and no
other op does, on the worst chip.  Layer: collectives (the halo
exchange's collective-permutes)."""


def read(facts):
    red = facts.get("trace")
    if red is None or red.window_s <= 0:
        return None
    if not any(d.collective_s > 0 for d in red.devices.values()):
        return None
    return max(100.0 * d.exposed_collective_s / red.window_s for d in red.devices.values())
