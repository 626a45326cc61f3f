"""mfu_pct: model FLOPs per token (bench/flops.py, recomputation not
counted) times the traced run's tokens/s, over the cell's chips times the
chip's bf16 peak (bench/peaks.py), in %."""


def read(rec):
    if rec["peak_flops"] is None:
        return None
    return (100.0 * rec["flops_per_token"] * rec["tokens_per_s"]
            / (rec["chips"] * rec["peak_flops"]))
