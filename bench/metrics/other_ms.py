"""other_ms: device self time per run of the round program in the ops under
none of the part scopes `grad`, `optimizer`, `sync` and `telemetry`, in
ms: the flat layout's worker views and gradient gather, and ops that lost
their `op_name` in lowering, the sync's reduce-scatter on a mesh among
them (device trace, first device of the cell; bench/scopes.py summarize)."""


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    return t["parts"]["other"] * 1e3
