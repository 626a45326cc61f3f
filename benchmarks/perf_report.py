"""§Perf report: assemble the hillclimb iteration tables (baseline vs each
variant) from experiments/dryrun + experiments/perf records.

Record paths resolve relative to the REPO ROOT, not the caller's cwd, and a
missing or malformed record is a WARNING (stderr) + a skipped row, never a
crash: CI runs this report on checkouts that carry only a subset of the
experiment records, and the report's job is to show what is there."""
from __future__ import annotations

import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __package__ in (None, ""):          # run as a script: python benchmarks/…
    sys.path.insert(0, _ROOT)
    sys.path.insert(0, os.path.join(_ROOT, "src"))   # repro.* for roofline

from benchmarks.roofline import terms

PAIRS = {
    "starcoder2-3b x train_4k (dp, 16x16)": [
        ("baseline (paper-faithful, full remat)",
         "experiments/dryrun/starcoder2-3b__train_4k__single.json"),
        ("it1a remat=save_collectives",
         "experiments/perf/sc2_train_save_coll.json"),
        ("it1b seq-parallel residual",
         "experiments/perf/sc2_train_seqshard.json"),
        ("it1c both", "experiments/perf/sc2_train_both.json"),
        ("it2 seq-parallel + no remat",
         "experiments/perf/sc2_train_seq_noremat.json"),
        ("it3 seq-parallel + dots remat",
         "experiments/perf/sc2_train_seq_dots.json"),
    ],
    "kimi-k2-1t x train_4k (fsdp, 16x16)": [
        ("baseline (global argsort dispatch)",
         "experiments/dryrun/kimi-k2-1t-a32b__train_4k__single.json"),
        ("it1 shard-local MoE dispatch",
         "experiments/perf/kimi_train_moeshard.json"),
        ("it2 + seq-parallel residual",
         "experiments/perf/kimi_train_moeshard_seq.json"),
        ("it3 + dots remat",
         "experiments/perf/kimi_train_ms_seq_dots.json"),
        ("it4 shard_map all-to-all dispatch",
         "experiments/perf/kimi_train_shardmap.json"),
        ("it5 shard_map + microbatch=8",
         "experiments/perf/kimi_train_sm_mb8.json"),
    ],
    "kimi-k2-1t x train_4k (fsdp, 2x16x16 multi-pod)": [
        ("baseline", "experiments/dryrun/kimi-k2-1t-a32b__train_4k__multi.json"),
        ("opt: sharded dispatch + microbatch=8",
         "experiments/perf/kimi_train_multi_ms_mb8.json"),
    ],
    "gemma3-4b x decode_32k (dp, 16x16)": [
        ("baseline (batch-sharded cache)",
         "experiments/dryrun/gemma3-4b__decode_32k__single.json"),
        ("it1 flash-decode cache layout (seq over model)",
         "experiments/perf/gemma3_decode_seqmodel.json"),
        ("it2 + bf16-native QK/PV dots",
         "experiments/perf/gemma3_decode_seqmodel_bf16.json"),
    ],
}


def _metrics(rec):
    if "local_step" in rec:
        h = rec["full"].get("h") or 4
        m = {k: rec["local_step"][k] + rec["sync"][k] / h
             for k in ("flops", "bytes_accessed", "collective_bytes_total")}
    else:
        key = "prefill" if "prefill" in rec else "decode"
        m = {k: rec[key][k]
             for k in ("flops", "bytes_accessed", "collective_bytes_total")}
    mem = rec["full"]["per_device_memory"]
    m["temp_gib"] = mem["temp_bytes"] / 2**30
    m.update(terms(m))
    m["bound_s"] = max(m["compute_s"], m["memory_s"], m["collective_s"])
    return m


def run(csv_rows: list | None = None) -> None:
    print("\n== §Perf hillclimb results (per device, per step/call) ==")
    for pair, variants in PAIRS.items():
        print(f"\n--- {pair} ---")
        base = None
        print(f"{'variant':42s} {'compute':>8s} {'memory':>8s} {'coll':>8s} "
              f"{'bound':>8s} {'temp':>9s} {'vs base':>8s}")
        for label, path in variants:
            full = os.path.join(_ROOT, path)
            if not os.path.exists(full):
                print(f"{label:42s}   (missing)")
                print(f"perf_report: WARNING skipping missing record {path}",
                      file=sys.stderr)
                continue
            try:
                rec = json.load(open(full))
            except (json.JSONDecodeError, OSError) as e:
                print(f"{label:42s}   (unreadable)")
                print(f"perf_report: WARNING unreadable record {path}: {e}",
                      file=sys.stderr)
                continue
            if not rec.get("ok", True):
                print(f"{label:42s}   FAILED")
                continue
            try:
                m = _metrics(rec)
            except KeyError as e:
                print(f"{label:42s}   (malformed)")
                print(f"perf_report: WARNING record {path} missing {e}",
                      file=sys.stderr)
                continue
            if base is None:
                base = m
            ratio = m["bound_s"] / base["bound_s"]
            print(f"{label:42s} {m['compute_s']:8.3f} {m['memory_s']:8.3f} "
                  f"{m['collective_s']:8.3f} {m['bound_s']:8.3f} "
                  f"{m['temp_gib']:8.1f}G {ratio:7.2%}")
            if csv_rows is not None:
                csv_rows.append((f"perf/{pair}/{label}",
                                 f"{1e6*m['bound_s']:.0f}", f"{ratio:.3f}"))


if __name__ == "__main__":
    run()
