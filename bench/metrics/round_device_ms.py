"""round_device_ms: device time of one run of the round program, in ms:
the part of its run in which a device operation runs (device trace,
first device of the cell)."""


def read(rec):
    t = rec["trace"]
    if t is None or t["round_busy_s"] is None:
        return None
    return t["round_busy_s"] * 1e3
