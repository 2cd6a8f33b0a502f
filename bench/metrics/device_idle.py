"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals / traced window), mean over chips,
in percent."""


def read(name, rec):
    s = rec.get("trace")
    if s is None or not s.devices:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
