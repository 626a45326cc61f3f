"""forward_ms: device self time per run of the round program in the local
step's forward, in ms: the ops whose innermost part scope is `grad` and
whose path holds no `transpose(` (device trace, first device of the cell;
bench/scopes.py summarize)."""


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    return t["parts"]["forward"] * 1e3
