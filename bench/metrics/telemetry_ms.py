"""telemetry_ms: device self time per run of the round program in the ops
whose innermost part scope is `telemetry` (the per-step grad-norm sum, the
engine's divergence, loss and norm sums, their collectives included), in
ms (device trace, first device of the cell; bench/scopes.py summarize)."""


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    return t["parts"]["telemetry"] * 1e3
