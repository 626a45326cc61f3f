"""host_gap_ms: the mean device-idle time between one round program's run
and the next, in ms (device trace; the host loop's share of a round)."""


def read(rec):
    t = rec["trace"]
    if t is None or t["round_gap_idle_s"] is None:
        return None
    return t["round_gap_idle_s"] * 1e3
