"""Roofline analysis (deliverable g): convert dry-run records into the three
roofline terms per (arch x shape x mesh), identify the dominant bottleneck,
and report MODEL_FLOPS / HLO_FLOPs utilization.

Hardware peaks: `PEAKS`, keyed by `jax.Device.device_kind`; a device kind
without published peaks is an error, never a default.  The dry-run meshes
model TPU v5e chips (`DRYRUN_DEVICE_KIND`).

Terms (seconds per training step / per serving call, PER DEVICE):
    compute    = HLO_FLOPs / peak
    memory     = HLO_bytes / HBM_bw        (upper bound: XLA's bytes-accessed
                                            counts per-op operands+results)
    collective = collective_bytes / ICI_bw

For training, per-step cost of the paper-faithful local method is
    local_step + sync / H        (QSR's whole point: sync amortized by H)
vs the data-parallel baseline's parallel_step.
"""
from __future__ import annotations

import glob
import json
import math
import os

from repro.configs import registry as R
from repro.errors import ConfigError
from repro.models import api, param as pm
from repro.models.param import is_def

# Published per-chip peaks.  TPU v5e — Google Cloud documentation, "TPU
# v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s inter-chip
# interconnect (taken here as ~50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """{flops, hbm_bw, ici_bw} per second for one chip of `device_kind`."""
    if device_kind not in PEAKS:
        raise ConfigError(
            f"no published peaks for device kind {device_kind!r}: add them "
            f"to benchmarks/roofline.py PEAKS with their source")
    return PEAKS[device_kind]


def model_params(arch: str) -> tuple[int, int]:
    """(total params N, active params N_active) — N_active discounts MoE
    expert weights by top_k/n_experts."""
    cfg = R.get_config(arch)
    defs = api.get_module(cfg).param_defs(cfg)
    total = active = 0
    for d in __import__("jax").tree.leaves(defs, is_leaf=is_def):
        n = math.prod(d.shape)
        total += n
        frac = (cfg.top_k / cfg.n_experts
                if cfg.n_experts and "experts" in d.axes else 1.0)
        active += int(n * frac)
    return total, active


def model_flops_per_step(arch: str, shape: dict, *, n_devices: int) -> float:
    """6 * N_active * D tokens (fwd+bwd), per device."""
    _, n_active = model_params(arch)
    tokens = shape["global_batch"] * shape["seq_len"]
    return 6.0 * n_active * tokens / n_devices


def terms(metrics: dict, device_kind: str = DRYRUN_DEVICE_KIND) -> dict:
    pk = peaks(device_kind)
    return {
        "compute_s": metrics["flops"] / pk["flops"],
        "memory_s": metrics["bytes_accessed"] / pk["hbm_bw"],
        "collective_s": metrics["collective_bytes_total"] / pk["ici_bw"],
    }


def dominant(t: dict) -> str:
    return max(t, key=t.get).replace("_s", "")


def analyze_record(rec: dict) -> dict | None:
    from repro.launch.shapes import SHAPES
    if not rec.get("ok"):
        return None
    arch, shape_name = rec["arch"], rec["shape"]
    shape = SHAPES[shape_name]
    nd = rec["n_devices"]
    out = {"arch": arch, "shape": shape_name, "mesh": rec["mesh"],
           "policy": rec["policy"]}

    if "local_step" in rec:
        h = rec["full"].get("h") or 4
        per_step = {k: rec["local_step"][k] + rec["sync"][k] / h
                    for k in ("flops", "bytes_accessed",
                              "collective_bytes_total")}
        t = terms(per_step)
        tp = terms(rec["parallel_step"])
        mf = model_flops_per_step(arch, {"global_batch": shape.global_batch,
                                         "seq_len": shape.seq_len},
                                  n_devices=nd)
        out.update({
            "fn": f"local_step+sync/H (H={h})", "terms": t,
            "dominant": dominant(t),
            "parallel_terms": tp, "parallel_dominant": dominant(tp),
            "model_flops": mf,
            "useful_flops_ratio": mf / max(per_step["flops"], 1.0),
            "sync_coll_bytes": rec["sync"]["collective_bytes_total"],
            "local_coll_bytes": rec["local_step"]["collective_bytes_total"],
            "parallel_coll_bytes":
                rec["parallel_step"]["collective_bytes_total"],
            "step_time_bound_s": max(t.values()),
            "parallel_step_time_bound_s": max(tp.values()),
        })
    else:
        key = "prefill" if "prefill" in rec else "decode"
        t = terms(rec[key])
        _, n_active = model_params(arch)
        tokens = rec[key + "_tokens"] if key + "_tokens" in rec else (
            shape.global_batch * (shape.seq_len if key == "prefill" else 1))
        mf = 2.0 * n_active * tokens / nd
        out.update({
            "fn": key, "terms": t, "dominant": dominant(t),
            "model_flops": mf,
            "useful_flops_ratio": mf / max(rec[key]["flops"], 1.0),
            "step_time_bound_s": max(t.values()),
        })
    out["memory_gib"] = {
        k: v / 2**30 for k, v in rec["full"]["per_device_memory"].items()}
    out["fits_hbm_16g"] = (
        rec["full"]["per_device_memory"]["argument_bytes"]
        + rec["full"]["per_device_memory"]["temp_bytes"]) < 16 * 2**30
    return out


def load_records(pattern: str = "experiments/dryrun/*.json") -> list[dict]:
    out = []
    for f in sorted(glob.glob(pattern)):
        if os.path.basename(f).startswith("test_"):
            continue
        with open(f) as fh:
            out.append(json.load(fh))
    return out


def run(csv_rows: list | None = None, pattern="experiments/dryrun/*.json"):
    recs = [analyze_record(r) for r in load_records(pattern)]
    recs = [r for r in recs if r]
    if not recs:
        print("\n== Roofline: no dry-run records found "
              "(write them with `python -m repro.launch.dryrun --out "
              "experiments/dryrun/<arch>__<shape>__single.json` first) ==")
        return
    print("\n== Roofline (per device, per step/call) ==")
    hdr = (f"{'arch':17s} {'shape':12s} {'mesh':8s} {'compute':>9s} "
           f"{'memory':>9s} {'collect':>9s} {'dom':>8s} {'useful':>7s}")
    print(hdr)
    for r in sorted(recs, key=lambda x: (x['arch'], x['shape'], x['mesh'])):
        t = r["terms"]
        print(f"{r['arch']:17s} {r['shape']:12s} {r['mesh']:8s} "
              f"{t['compute_s']:9.4f} {t['memory_s']:9.4f} "
              f"{t['collective_s']:9.4f} {r['dominant']:>8s} "
              f"{100*r['useful_flops_ratio']:6.1f}%")
        if csv_rows is not None:
            csv_rows.append((
                f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}",
                f"{1e6*r['step_time_bound_s']:.1f}",
                r["dominant"]))


if __name__ == "__main__":
    run()
