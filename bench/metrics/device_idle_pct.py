"""device_idle_pct: the share of the traced window in which no operation
ran on the device, on the busiest of the cell's devices, in %."""


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - max(t["busy_s"].values()) / t["window_s"])
