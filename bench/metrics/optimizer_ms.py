"""optimizer_ms: device self time per run of the round program in the ops
whose innermost part scope is `optimizer` (AdamW's update), in ms (device
trace, first device of the cell; bench/scopes.py summarize)."""


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    return t["parts"]["optimizer"] * 1e3
