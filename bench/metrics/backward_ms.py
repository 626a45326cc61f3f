"""backward_ms: device self time per run of the round program in the local
step's backward, in ms: the ops whose innermost part scope is `grad` and
whose path holds a `transpose(`, the remat recompute among them (device
trace, first device of the cell; bench/scopes.py summarize)."""


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    return t["parts"]["backward"] * 1e3
