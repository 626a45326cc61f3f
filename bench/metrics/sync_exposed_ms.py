"""sync_exposed_ms: the part of sync_collective_ms in which no other op
runs on the same device: the exchange that computation does not hide, per
run of the round program, in ms, on the device where it is largest (device
trace).  Nothing where no collective ran, as on one chip."""


def read(rec):
    t = rec["trace"]
    if t is None or t["collective_exposed_s"] is None:
        return None
    return t["collective_exposed_s"] * 1e3
