"""sync_collective_ms: device time in the round program's collective ops
that are not in the program's `telemetry` scope (bench/scopes.py
sync_collectives: all-reduce, reduce-scatter, all-gather,
collective-permute, all-to-all and their async halves, classified by HLO
opcode from the compiled round), per run of the round program, in ms, on
the device where it is largest (device trace).  The divergence telemetry's
all-reduce of the parameters is left out.  Nothing where no collective
ran, as on one chip."""


def read(rec):
    t = rec["trace"]
    if t is None or t["collective_s"] is None:
        return None
    return t["collective_s"] * 1e3
