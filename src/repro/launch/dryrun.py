import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # simulated host devices
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("REPRO_DRYRUN_DEVICES", "512")).strip()
# ^ MUST run before any other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination on placeholder host devices and record roofline inputs.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-3b \
      --shape train_4k [--multi-pod] [--parallel-baseline] [--out FILE]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs.base import RunConfig
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, build_case


def run_one(arch, shape, *, multi_pod, policy=None,
            parallel_baseline=False, run_cfg=None,
            engine="legacy", layout="tree", sync="blocking",
            overlap_depth=0, quantize=False, wire="auto", verbose=True):
    from repro.configs import registry as R

    policy = policy or R.get_policy(arch)
    if run_cfg is None and (quantize or wire != "auto"):
        run_cfg = RunConfig(sharding=policy, sync_wire=wire,
                            sync_quantize=quantize or wire == "ring-int8")
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    case = build_case(arch, shape, mesh, policy=policy,
                      run_cfg=run_cfg, parallel_baseline=parallel_baseline,
                      engine=engine, layout=layout, sync=sync,
                      overlap_depth=overlap_depth)
    t0 = time.time()
    with mesh:
        jitted = jax.jit(case.fn, in_shardings=case.in_shardings,
                         out_shardings=case.out_shardings)
        lowered = jitted.lower(*case.args)
        compiled = lowered.compile()
    t1 = time.time()
    stats = hlo_analysis.summarize(compiled, n_devices=n_dev)
    rec = {
        "arch": arch, "shape": shape, "policy": policy,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "fn": case.meta["fn_name"],
        "steps_per_program": case.meta.get("steps_per_program", 1),
        "workers": case.meta.get("w"),
        "h": case.meta.get("h"),
        "hp": case.meta.get("hp"),
        "layout": case.meta.get("layout", "tree"),
        "sync": case.meta.get("sync", "blocking"),
        "quantize": bool(run_cfg.sync_quantize) if run_cfg else False,
        "wire": getattr(run_cfg, "sync_wire", "auto") if run_cfg else "auto",
        "overlap_depth": case.meta.get("overlap_depth"),
        "pending_leaves": case.meta.get("pending_leaves"),
        "ring": case.meta.get("ring"),
        "kv_len": case.meta.get("kv_len"),
        "compile_s": round(t1 - t0, 1),
        **stats,
    }
    if verbose:
        mem = stats["per_device_memory"]
        print(f"[{arch} x {shape} x {rec['mesh']} {rec['fn']}] "
              f"compile {rec['compile_s']}s  "
              f"flops/dev {stats['flops']:.3e}  "
              f"bytes/dev {stats['bytes_accessed']:.3e}  "
              f"coll/dev {stats['collective_bytes_total']:.3e}  "
              f"arg {mem['argument_bytes']/2**30:.2f}GiB "
              f"temp {mem['temp_bytes']/2**30:.2f}GiB")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--policy", default=None, choices=["dp", "fsdp", None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--parallel-baseline", action="store_true")
    ap.add_argument("--engine", default="legacy",
                    choices=["legacy", "bucketed"],
                    help="train_round flavor to lower: the seed's exact-H "
                         "program or the RoundEngine's padded+masked bucket")
    ap.add_argument("--param-layout", default="tree",
                    choices=["tree", "flat", "flat_sharded"],
                    help="flat: lower the round over FlatParamSpace dtype "
                         "buckets (requires --engine bucketed; the sync "
                         "drops to one all-reduce per bucket — see "
                         "collective_counts in the record); flat_sharded: "
                         "ShardedFlatSpace chunks — state stored 1/S per "
                         "device, the sync one reduce_scatter + one "
                         "all_gather per bucket (collective_result_bytes "
                         "shows the scatter leg landing 1/W per device)")
    ap.add_argument("--sync", default="blocking",
                    choices=["blocking", "overlap"],
                    help="overlap (requires --engine bucketed): lower the "
                         "pending-threaded steady-state round — "
                         "fn(state, pending, ...) -> (state, new_pending, "
                         "metrics), the program the RoundEngine runs under "
                         "--sync overlap; the in-flight payload stays "
                         "worker-sharded across the program boundary")
    ap.add_argument("--overlap-depth", type=int, default=0,
                    help="local steps lowered before the deferred "
                         "gather/apply (--sync overlap)")
    ap.add_argument("--quantize", action="store_true",
                    help="lower the int8-quantized sync (integer-code "
                         "payloads on the RS/AG legs + one tiny amax pmax)")
    ap.add_argument("--wire", default="auto", choices=["auto", "ring-int8"],
                    help="quantized payload wire mode (README §Wire modes); "
                         "ring-int8 lowers the W-hop re-quantizing ppermute "
                         "ring — collective_counts shows the s8 "
                         "collective-permutes (implies --quantize; needs "
                         "--param-layout flat_sharded)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from repro.configs import registry as R

    archs = R.ASSIGNED if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    records, failures = [], []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    records.append(run_one(arch, shape, multi_pod=mp,
                                           policy=args.policy,
                                           parallel_baseline=args.parallel_baseline,
                                           engine=args.engine,
                                           layout=args.param_layout,
                                           sync=args.sync,
                                           overlap_depth=args.overlap_depth,
                                           quantize=args.quantize,
                                           wire=args.wire))
                except Exception as e:  # a failure here is a bug in the system
                    traceback.print_exc()
                    failures.append({"arch": arch, "shape": shape,
                                     "mesh": "2x16x16" if mp else "16x16",
                                     "error": repr(e)})
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"records": records, "failures": failures}, f, indent=1)
    print(f"\n{len(records)} ok, {len(failures)} failed")
    if failures:
        for f_ in failures:
            print("FAIL:", f_)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
