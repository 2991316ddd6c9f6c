"""Share of the traced window in which no op runs on the device,
averaged over the chips of the cell.  Layer: device."""


def read(facts):
    red = facts.get("trace")
    if red is None or red.window_s <= 0 or not red.devices:
        return None
    busy = sum(d.busy_s for d in red.devices.values()) / len(red.devices)
    return 100.0 * (1.0 - busy / red.window_s)
