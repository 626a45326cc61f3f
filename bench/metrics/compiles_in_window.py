"""compiles_in_window: round programs the engine compiled inside the
window (its compile_stats counter, after minus before)."""


def read(rec):
    return rec["compiles_in_window"]
