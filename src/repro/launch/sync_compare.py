import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # simulated host devices
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("REPRO_DRYRUN_DEVICES", "8")).strip()
# ^ MUST run before any other import: jax locks the device count on first init.

"""Sync lowering compared across param layouts on a debug sharded mesh.

Compiles the every-H-steps sync under the tree / flat / flat_sharded param
layouts and reports, per layout, what the wire actually sees: collective op
counts per kind (hlo_analysis.collective_counts — the latency/launch axis),
full-tensor bytes per sync (collective_bytes — the bandwidth axis), and
per-leg landing bytes (collective_result_bytes — where the sharded layout's
scatter-leg ~W x drop shows).  This is the measurement behind the layout
acceptance claims: flat = one all-reduce per dtype bucket instead of one
per pytree leaf; flat_sharded = one reduce_scatter + one all_gather per
bucket instead of the full all-reduce, with the scatter leg landing 1/W of
the bucket per device.

Run as a module (subprocess-safe: the device-count pin above must precede
any jax init, so callers shell out rather than import):

  PYTHONPATH=src python -m repro.launch.sync_compare \
      --arch starcoder2-3b [--param-layout flat_sharded] [--policy fsdp] \
      [--mesh 4x2 | --mesh 2x2x2] [--smoke] [--quantize] [--momentum 0.9]

A three-field mesh (PxDxM) adds a pod axis — the fsdp policy's worker axis,
so `--mesh 2x2x2 --policy fsdp` exercises the multi-pod QSR configuration
where each pod is one worker and buckets chunk over (data, model).

Prints one JSON object; benchmarks/table1_comm.py, tests/test_flat.py and
tests/test_sharded.py consume it.
"""
import argparse
import json

import jax

from repro.analysis import rules
from repro.configs.base import RunConfig
from repro.errors import ConfigError
from repro.launch import hlo_analysis
from repro.launch.mesh import make_debug_mesh
from repro.launch.shapes import build_calib_case

LAYOUTS = ("tree", "flat", "flat_sharded")


def compare(arch: str = "starcoder2-3b", *, smoke: bool = True,
            quantize: bool = False, momentum: float = 0.0,
            wire: str = "auto",
            n_data: int = 4, n_model: int = 2, pods: int = 0,
            policy: str = "dp",
            layouts: tuple[str, ...] = LAYOUTS) -> dict:
    """{layout: {collective_counts, collective_bytes, collective_leg_bytes,
    all_reduce_ops, reduce_scatter_ops, all_gather_ops, bytes_on_wire,
    scatter_leg_bytes, n_leaves, n_buckets, payload_bytes_by_dtype, ...}}
    for the policy's sync.  wire="ring-int8" swaps the one-shot RS for the
    re-quantizing ppermute ring (flat layouts only; requires quantize)."""
    from repro.configs import registry as R

    cfg = R.get_smoke_config(arch) if smoke else R.get_config(arch)
    run_cfg = RunConfig(sharding=policy, sync_quantize=quantize,
                        outer_momentum=momentum, sync_wire=wire)
    mesh = make_debug_mesh(n_data, n_model, pods=pods)
    out = {"_config": {"arch": arch, "smoke": smoke, "quantize": quantize,
                       "momentum": momentum, "policy": policy, "wire": wire,
                       "mesh": [d for d in ((pods,) if pods else ())
                                + (n_data, n_model)]}}
    for layout in layouts:
        case = build_calib_case(cfg, "train_4k", mesh, policy=policy,
                                run_cfg=run_cfg, fn_kind="sync",
                                layout=layout)
        with mesh:
            compiled = jax.jit(case.fn, in_shardings=case.in_shardings,
                               out_shardings=case.out_shardings
                               ).lower(*case.args).compile()
        hlo = compiled.as_text()
        # the scale-vs-payload classification (the quantized sharded sync
        # is allowed ONE tiny amax-fold all-reduce — 4 bytes per model
        # tensor — and zero payload-sized ones; the ring's per-hop f32
        # scales are scalar-sized and classified with the same threshold)
        # lives in hlo_analysis.payload_profile, shared with the audit CLI
        rec = hlo_analysis.payload_profile(hlo, n_leaves=case.meta["n_leaves"])
        rec["n_buckets"] = case.meta["n_buckets"]
        rec["workers"] = case.meta["w"]
        rec["host_callback_lines"] = hlo_analysis.host_callbacks(hlo)
        rec["degenerate_collectives"] = hlo_analysis.degenerate_collectives(hlo)
        # attach the declarative rule verdicts: tests assert the layout
        # acceptance claims through this one registry (repro.analysis.rules)
        # instead of re-deriving counts per test file
        rule_cfg = {"kind": "sync", "layout": layout, "sync": "blocking",
                    "wire": wire, "quantize": quantize, "policy": policy,
                    "workers": case.meta["w"]}
        rec["rules"] = rules.evaluate(rule_cfg, rec)
        rec["rules_failed"] = rules.failed(rec["rules"])
        out[layout] = rec
    return out


def exec_compare(arch: str = "starcoder2-3b", *, smoke: bool = True,
                 quantize: bool = False, momentum: float = 0.0,
                 wire: str = "auto",
                 n_data: int = 4, n_model: int = 2, pods: int = 0,
                 policy: str = "dp", rounds: int = 3,
                 layouts: tuple[str, ...] = LAYOUTS) -> dict:
    """EXECUTE the sync under each layout on the debug mesh and compare the
    multi-round trajectories against the mesh-less flat path (the reference
    every bitwise test in tests/ anchors to).

    Each round perturbs every worker's params with the same host-generated
    noise and runs the layout's jitted sync.  Quantized, all layouts must
    agree BITWISE with the reference on any mesh: the worker mean runs over
    integer codes (core/sync.py RS-domain rule), so neither GSPMD's
    all-reduce ordering nor the explicit reduce_scatter changes a single
    bit.  Unquantized f32 means are only order-independent for 2 workers.

    wire="ring-int8" is the deliberate exception: per-hop requantization is
    chunking-dependent, so the mesh trajectories are asserted within
    `ring_tolerance` of the host reference (reported as `within_tol`), never
    bitwise — the drift is the price of int8-on-every-hop and is measured
    here and in benchmarks/sde_drift.py.
    """
    import numpy as np

    from repro.configs import registry as R
    from repro.core import flat as F, local_update as LU
    from repro.core.sync import make_sync, ring_tolerance
    from repro.models import api, param as pm

    cfg = R.get_smoke_config(arch) if smoke else R.get_config(arch)
    run_cfg = RunConfig(sharding=policy, sync_quantize=quantize,
                        outer_momentum=momentum, sync_wire=wire)
    mesh = make_debug_mesh(n_data, n_model, pods=pods)
    w = pm.worker_count(policy, mesh)
    waxes = pm.worker_mesh_axes(policy, mesh)
    saxes = tuple(a for a in mesh.axis_names if a not in waxes)
    sizes = pm.mesh_axis_sizes(mesh)
    shards = int(np.prod([sizes[a] for a in waxes + saxes]))

    params = pm.init_params(api.get_module(cfg).param_defs(cfg),
                            jax.random.PRNGKey(0))
    base = LU.init_state(cfg, run_cfg, params, w)
    base.pop("opt")          # the sync never touches optimizer state

    # per-round worker perturbations, shared by every layout (host numpy)
    rng = np.random.RandomState(7)
    noises = [jax.tree.map(lambda x: (rng.randn(w, *np.shape(x)) * 0.01
                                      ).astype(np.float32), params)
              for _ in range(rounds)]

    def run_layout(layout, with_mesh: bool):
        from jax.sharding import NamedSharding, PartitionSpec as P
        if layout == "tree":
            spec = None
        elif layout == "flat":
            spec = F.FlatParamSpace(params)
        else:
            spec = (F.ShardedFlatSpace(params, shards, mesh=mesh,
                                       worker_axes=waxes, shard_axes=saxes)
                    if with_mesh else F.ShardedFlatSpace(params, shards))
        if spec is None:
            state = dict(base)
        else:
            state = {k: (spec.flatten(v, lead=1) if k == "params"
                         else spec.flatten(v)) for k, v in base.items()}
        if with_mesh and spec is not None:
            sspec = F.flat_state_specs(run_cfg, waxes, spec)
            state = {k: {b: jax.device_put(v[b],
                                           NamedSharding(mesh, sspec[k][b]))
                         for b in v} for k, v in state.items()}
        sync = jax.jit(make_sync(run_cfg, spec=spec))
        for noise in noises:
            if spec is None:
                perturbed = jax.tree.map(
                    lambda p, n: (p + n.astype(p.dtype)), state["params"],
                    noise)
            else:
                nb = spec.flatten(noise, lead=1)
                perturbed = {b: state["params"][b] + nb[b].astype(
                    state["params"][b].dtype) for b in nb}
            state = dict(state, params=perturbed)
            with mesh:
                state = sync(state)
        if spec is None:
            return state
        return {k: (spec.unflatten(v, lead=1) if k == "params"
                    else spec.unflatten(v)) for k, v in state.items()}

    ref = run_layout("flat_sharded", with_mesh=False)   # host path reference
    out = {"rounds": rounds, "workers": w, "quantize": quantize,
           "momentum": momentum, "wire": wire,
           "reference": "flat_sharded(no mesh)"}
    if wire == "ring-int8":
        amax_d = max(float(np.max(np.abs(l)))
                     for noise in noises for l in jax.tree.leaves(noise))
        out["ring_tol"] = ring_tolerance(w, amax_d, rounds)
    for layout in layouts:
        got = run_layout(layout, with_mesh=True)
        diffs = [float(np.max(np.abs(np.asarray(a, np.float32)
                                     - np.asarray(b, np.float32))))
                 if np.size(np.asarray(a)) else 0.0
                 for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref))]
        md = max(diffs)
        out[layout] = {"max_abs_diff": md, "bitwise": md == 0.0}
        if wire == "ring-int8":
            out[layout]["within_tol"] = md <= out["ring_tol"]
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--full", action="store_true",
                    help="production config (default: smoke, CPU-runnable)")
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--momentum", type=float, default=0.0)
    ap.add_argument("--policy", default="dp", choices=["dp", "fsdp"])
    ap.add_argument("--param-layout", default=None,
                    help="compare only these layouts, comma-separated "
                         "(default: all three)")
    ap.add_argument("--mesh", default="4x2",
                    help="debug mesh data x model, or pod x data x model; "
                         "8x1 = pure dp, where tree/flat move identical "
                         "bytes and flat_sharded's scatter leg lands 1/W "
                         "per device (with model sharding, tree all-reduces "
                         "shard-local bytes)")
    ap.add_argument("--exec", dest="exec_", action="store_true",
                    help="also EXECUTE the sync per layout on the mesh and "
                         "compare multi-round trajectories against the "
                         "mesh-less flat path (bitwise when --quantize: "
                         "the integer-code mean is order-independent)")
    ap.add_argument("--exec-rounds", type=int, default=3)
    ap.add_argument("--wire", default="auto", choices=["auto", "ring-int8"],
                    help="quantized payload wire mode: auto = exact Sq "
                         "contract in wire_dtype(W) (int16/int32); "
                         "ring-int8 = W-hop re-quantizing ppermute ring, "
                         "int8 on every hop, tolerance-based (not bitwise); "
                         "implies --quantize and flat layouts only")
    ap.add_argument("--out", default=None,
                    help="also write the JSON record to this path (the "
                         "multi-device CI matrix publishes these artifacts)")
    args = ap.parse_args()
    dims = [int(x) for x in args.mesh.split("x")]
    pods, n_data, n_model = ([0] + dims if len(dims) == 2 else dims)
    if args.wire == "ring-int8":
        args.quantize = True        # the ring carries int8 codes by definition
    if args.param_layout:
        layouts = tuple(args.param_layout.split(","))
        bad = [l for l in layouts if l not in LAYOUTS]
        if bad:
            raise ConfigError(f"unknown layouts {bad}; pick from {LAYOUTS}")
    else:
        layouts = LAYOUTS
    if args.wire == "ring-int8":
        layouts = tuple(l for l in layouts if l != "tree") or ("flat_sharded",)
    out = compare(args.arch, smoke=not args.full,
                  quantize=args.quantize,
                  momentum=args.momentum, wire=args.wire,
                  n_data=n_data, n_model=n_model, pods=pods,
                  policy=args.policy, layouts=layouts)
    if args.exec_:
        out["exec"] = exec_compare(args.arch, smoke=not args.full,
                                   quantize=args.quantize,
                                   momentum=args.momentum, wire=args.wire,
                                   n_data=n_data, n_model=n_model, pods=pods,
                                   policy=args.policy,
                                   rounds=args.exec_rounds, layouts=layouts)
    text = json.dumps(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    main()
