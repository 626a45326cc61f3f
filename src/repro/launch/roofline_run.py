import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # simulated host devices
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("REPRO_DRYRUN_DEVICES", "512")).strip()

"""Roofline extraction for one (arch x shape x mesh).

Two-phase measurement (see EXPERIMENTS.md §Dry-run methodology):
  1. FULL-config compile (scan mode, fast): proves the program lowers +
     compiles on the production mesh and yields memory_analysis().
  2. CALIBRATION compiles: the same program at two reduced depths with every
     scan unrolled (exact HLO costs), fit cost(L)=a*L+b, extrapolate to full
     depth.  Training decomposes into local_step + sync (+ parallel_step
     baseline), which exposes QSR's  coll(step) = local + sync/H  scaling.

Writes one JSON record per invocation:
  PYTHONPATH=src python -m repro.launch.roofline_run --arch X --shape Y \
      [--multi-pod] --out experiments/dryrun/X__Y__MESH.json
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs.base import RunConfig
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import (SHAPES, build_calib_case, build_case,
                                 calib_sizes, with_depth)

_METRICS = ("flops", "bytes_accessed", "collective_bytes_total",
            "dci_bytes")
_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _compile_case(case, mesh):
    t0 = time.time()
    with mesh:
        jitted = jax.jit(case.fn, in_shardings=case.in_shardings,
                         out_shardings=case.out_shardings)
        compiled = jitted.lower(*case.args).compile()
    stats = hlo_analysis.summarize(compiled, n_devices=mesh.devices.size)
    stats["compile_s"] = round(time.time() - t0, 1)
    return stats


def _flat_metrics(stats):
    out = {m: stats[m] for m in _METRICS}
    for k in _COLL_KINDS:
        out[f"coll:{k}"] = stats["collective_bytes"][k]
    return out


def _extrapolate(m1, m2, l1, l2, lf):
    out = {}
    for k in m1:
        slope = (m2[k] - m1[k]) / (l2 - l1)
        out[k] = max(0.0, slope * lf + (m1[k] - slope * l1))
    return out


def _calibrate(cfg, shape, mesh, policy, run_cfg, fn_kind):
    l1, l2, lf = calib_sizes(cfg)
    os.environ["REPRO_DRYRUN_UNROLL"] = "1"
    try:
        s1 = _compile_case(build_calib_case(with_depth(cfg, l1), shape, mesh,
                                            policy=policy, run_cfg=run_cfg,
                                            fn_kind=fn_kind), mesh)
        s2 = _compile_case(build_calib_case(with_depth(cfg, l2), shape, mesh,
                                            policy=policy, run_cfg=run_cfg,
                                            fn_kind=fn_kind), mesh)
    finally:
        os.environ["REPRO_DRYRUN_UNROLL"] = "0"
    # extrapolate in units of l1 layers (one pattern block / hybrid group)
    ext = _extrapolate(_flat_metrics(s1), _flat_metrics(s2),
                       1.0, l2 / l1, lf / l1)
    ext["calib_compile_s"] = s1["compile_s"] + s2["compile_s"]
    return ext


def run_pair(arch, shape_name, *, multi_pod, policy=None, run_cfg=None,
             calibrate=True, **run_kw):
    from repro.configs import registry as R

    policy = policy or R.get_policy(arch)
    run_cfg = run_cfg or RunConfig(sharding=policy, **run_kw)
    cfg = R.get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    if getattr(run_cfg, "moe_dispatch", "auto") == "shard_map":
        from repro.models import moe as _moe
        _moe.set_dispatch("shard_map", mesh)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "policy": policy,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "n_devices": mesh.devices.size}

    # ---- phase 1: full-config lowering proof + memory ----
    os.environ["REPRO_DRYRUN_UNROLL"] = "0"
    full = build_case(arch, shape_name, mesh, policy=policy, run_cfg=run_cfg)
    stats = _compile_case(full, mesh)
    rec["full"] = {"fn": full.meta["fn_name"], "compile_s": stats["compile_s"],
                   "per_device_memory": stats["per_device_memory"],
                   "raw_once_per_loop": _flat_metrics(stats),
                   **{k: full.meta.get(k) for k in
                      ("w", "b_loc", "h", "ring", "kv_len")}}

    if not calibrate:
        return rec

    # ---- phase 2: calibrated exact per-step costs ----
    if shape.mode == "train":
        rec["local_step"] = _calibrate(cfg, shape_name, mesh, policy, run_cfg,
                                       "local_step")
        rec["sync"] = _calibrate(cfg, shape_name, mesh, policy, run_cfg,
                                 "sync")
        rec["parallel_step"] = _calibrate(cfg, shape_name, mesh, policy,
                                          run_cfg, "parallel_step")
    else:
        kind = "prefill" if shape.mode == "prefill" else "decode"
        rec[kind] = _calibrate(cfg, shape_name, mesh, policy, run_cfg, kind)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--policy", default=None)
    ap.add_argument("--no-calibrate", action="store_true")
    ap.add_argument("--h", type=int, default=None)
    ap.add_argument("--cache-layout", default="batch",
                    choices=["batch", "seq_model"])
    ap.add_argument("--remat", default="1")
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "save_collectives", "dots"])
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--moe-shards", type=int, default=1)
    ap.add_argument("--moe-dispatch", default="auto",
                    choices=["auto", "shard_map"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    try:
        rec = run_pair(args.arch, args.shape, multi_pod=args.multi_pod,
                       policy=args.policy, calibrate=not args.no_calibrate,
                       cache_layout=args.cache_layout,
                       remat=bool(int(args.remat)),
                       remat_policy=args.remat_policy,
                       seq_shard_activations=args.seq_shard,
                       moe_dispatch_shards=args.moe_shards,
                       moe_dispatch=args.moe_dispatch,
                       microbatch=args.microbatch)
        rec["variant"] = {"cache_layout": args.cache_layout,
                          "remat": bool(int(args.remat)),
                          "remat_policy": args.remat_policy,
                          "seq_shard": args.seq_shard,
                          "moe_shards": args.moe_shards,
                          "moe_dispatch": args.moe_dispatch,
                          "microbatch": args.microbatch}
        rec["ok"] = True
    except Exception as e:
        traceback.print_exc()
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multi_pod else "16x16",
               "ok": False, "error": repr(e)}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)
    print(json.dumps({k: v for k, v in rec.items()
                      if k in ("arch", "shape", "mesh", "ok", "error")}))


if __name__ == "__main__":
    main()
