import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # simulated host devices
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("REPRO_DRYRUN_DEVICES", "8")).strip()
# ^ MUST run before any other import: jax locks the device count on first init.

"""Collective autotuner for the every-H-steps sync + the CI perf trajectory.

For one (mesh, policy) this module enumerates candidate sync *plans* —
    wire   ∈ {f32 (unquantized), int-codes (exact Σq in wire_dtype(W)),
              ring-int8 (re-quantizing ppermute ring)}
    sync   ∈ {blocking, overlap depth 1, overlap depth 2}
— and scores each on three measured axes:

  * bytes_on_wire — parsed from the optimized HLO of the lowered sync
    (launch/hlo_analysis), per wire: what one sync actually puts on the
    interconnect, including the payload dtype split that proves the ring is
    s8-only.
  * drift — the plan's sync EXECUTED for `drift_rounds` against the exact
    unquantized host mean on identical worker noise: max |param diff| at the
    end.  Measured, never assumed (the ring's per-hop requantization bound
    `ring_tolerance` disqualifies a plan that exceeds it).  Runs in a
    watchdog subprocess (`measure_drift_guarded`) on the CPU backend: XLA's
    in-process CPU collective rendezvous can rarely deadlock on an
    oversubscribed host, so a hung measurement is killed and retried
    instead of hanging the tuner.  On an accelerator it runs in-process.
  * s_per_round — full RoundEngine rounds (local steps + sync) timed on the
    mesh, the wall-clock axis that catches a plan whose byte win costs too
    many kernel launches.

The chosen plan minimizes (bytes_on_wire, s_per_round) lexicographically
among plans whose drift passes — bytes are what scale to the production
interconnect, wall-clock breaks ties between plans that move the same bytes
(e.g. ring+blocking vs ring+overlap).

The emitted record (BENCH_sync.json, schema "bench_sync/v1", README §Perf
trajectory) is the repo's perf trajectory point; `--append FILE` collects
points from several (mesh, policy) legs of one CI run into a single
trajectory file (schema "bench_sync_trajectory/v1": {"points": [rec, ...]})
— the CI `bench` job appends the dp 4x2 and fsdp 2x2x2 pod-mesh points.
`--baseline` gates a run against the committed
benchmarks/bench_sync_baseline.json:

  * bytes_on_wire of the chosen plan must not grow,
  * the chosen plan's s/round RATIO to the in-run f32+blocking reference
    must not regress more than --regress-frac (default 10%) vs the
    baseline's ratio — a ratio so a slower CI machine cannot fail the gate,
  * the ring's bytes reduction vs the exact int-codes wire must stay >= 2x
    (the acceptance floor).

Run as a module (subprocess-safe: the device pin above precedes jax init):

  PYTHONPATH=src python -m repro.launch.autotune --mesh 4x2 --policy dp \
      --out BENCH_sync.json --baseline benchmarks/bench_sync_baseline.json
"""
import argparse
import json
import sys
import time

import jax

from repro.configs.base import RunConfig
from repro.launch import hlo_analysis
from repro.launch.mesh import make_debug_mesh
from repro.launch.shapes import build_calib_case

SCHEMA = "bench_sync/v1"

# (name, quantize, sync_wire) — every candidate wire for the sync payload
WIRES = (("f32", False, "auto"),
         ("int-codes", True, "auto"),
         ("ring-int8", True, "ring-int8"))
# joint overlap-depth enumeration: depth 0 IS blocking (bitwise), deeper
# depths trade staleness for hidden gather time — the same frontier the
# adaptive controller (core/controller.py) rides at run time
SYNCS = (("blocking", 0), ("overlap", 1), ("overlap", 2))
TRAJECTORY_SCHEMA = "bench_sync_trajectory/v1"


def _wire_dtype_name(wire_name: str, w: int) -> str:
    from repro.core.sync import wire_dtype
    if wire_name == "f32":
        return "float32"
    if wire_name == "ring-int8":
        return "int8"
    return str(jax.numpy.dtype(wire_dtype(w)))


def _mesh_tuple(mesh: str):
    dims = [int(x) for x in mesh.split("x")]
    return ([0] + dims if len(dims) == 2 else dims)


def lower_wire(cfg, run_cfg, mesh, policy: str) -> dict:
    """Compile the flat_sharded sync for one wire and read the wire truth
    off the optimized HLO: total bytes, per-dtype payload split, op counts.
    Same payload/scale classification as launch/sync_compare."""
    case = build_calib_case(cfg, "train_4k", mesh, policy=policy,
                            run_cfg=run_cfg, fn_kind="sync",
                            layout="flat_sharded")
    with mesh:
        compiled = jax.jit(case.fn, in_shardings=case.in_shardings,
                           out_shardings=case.out_shardings
                           ).lower(*case.args).compile()
    hlo = compiled.as_text()
    counts = hlo_analysis.collective_counts(hlo)
    nbytes = hlo_analysis.collective_bytes(hlo)
    fold_limit = 4 * case.meta["n_leaves"] + 64
    payload = [op for op in hlo_analysis.collective_ops(hlo)
               if op["bytes_full"] > fold_limit]
    by_dtype = {}
    for op in payload:
        by_dtype[op["dtype"]] = by_dtype.get(op["dtype"], 0) + op["bytes_full"]
    return {
        "bytes_on_wire": sum(v for k, v in nbytes.items() if k != "dci"),
        "payload_bytes_by_dtype": by_dtype,
        "collective_counts": {k: v for k, v in counts.items() if v},
        "n_buckets": case.meta["n_buckets"],
    }


def measure_drift(cfg, run_cfg, mesh, policy: str, *, rounds: int = 3,
                  seed: int = 7) -> dict:
    """EXECUTE the plan's sync on the mesh for `rounds` and report the end
    divergence from the exact unquantized host worker-mean on identical
    noise — the measured cost of the wire compression.  Returns
    {drift, tol, within_tol}; tol is `ring_tolerance` of the observed noise
    amax (the analytic bound the ring must beat; exact wires get the f32
    mean-reassociation allowance instead)."""
    import numpy as np

    from repro.core import flat as F, local_update as LU
    from repro.core.sync import make_sync, ring_tolerance
    from repro.models import api, param as pm

    w = pm.worker_count(policy, mesh)
    waxes = pm.worker_mesh_axes(policy, mesh)
    saxes = tuple(a for a in mesh.axis_names if a not in waxes)
    sizes = pm.mesh_axis_sizes(mesh)
    shards = int(np.prod([sizes[a] for a in waxes + saxes]))

    params = pm.init_params(api.get_module(cfg).param_defs(cfg),
                            jax.random.PRNGKey(0))
    base = LU.init_state(cfg, run_cfg, params, w)
    base.pop("opt")
    rng = np.random.RandomState(seed)
    noises = [jax.tree.map(lambda x: (rng.randn(w, *np.shape(x)) * 0.01
                                      ).astype(np.float32), params)
              for _ in range(rounds)]

    def run(rc, with_mesh: bool):
        from jax.sharding import NamedSharding
        spec = (F.ShardedFlatSpace(params, shards, mesh=mesh,
                                   worker_axes=waxes, shard_axes=saxes)
                if with_mesh else F.ShardedFlatSpace(params, shards))
        st = {k: (spec.flatten(v, lead=1) if k == "params"
                  else spec.flatten(v))
              for k, v in base.items()
              if k == "params" or rc.sync_quantize or rc.outer_momentum > 0.0}
        if with_mesh:
            sspec = F.flat_state_specs(rc, waxes, spec)
            st = {k: {b: jax.device_put(v[b],
                                        NamedSharding(mesh, sspec[k][b]))
                      for b in v} for k, v in st.items()}
        sync = jax.jit(make_sync(rc, spec=spec))
        for noise in noises:
            nb = spec.flatten(noise, lead=1)
            st = dict(st, params={b: st["params"][b] + nb[b].astype(
                st["params"][b].dtype) for b in nb})
            if with_mesh:
                # drain the dispatch queue around the collective program: a
                # sync needs all n_devices executions in flight at once, and
                # the rendezvous is least likely to starve when they are the
                # only work pending.  This narrows the race but cannot close
                # it — measure_drift_guarded's watchdog is the actual guard.
                jax.block_until_ready(st)
            with mesh:
                st = sync(st)
            if with_mesh:
                jax.block_until_ready(st)
        return {k: (spec.unflatten(v, lead=1) if k == "params"
                    else spec.unflatten(v)) for k, v in st.items()}

    exact = run(RunConfig(sharding=policy), with_mesh=False)
    got = run(run_cfg, with_mesh=True)
    drift = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                    - np.asarray(b, np.float32))))
                if np.size(np.asarray(a)) else 0.0
                for a, b in zip(jax.tree.leaves(got["params"]),
                                jax.tree.leaves(exact["params"])))
    amax_d = max(float(np.max(np.abs(l)))
                 for noise in noises for l in jax.tree.leaves(noise))
    tol = ring_tolerance(w, amax_d, rounds)
    return {"drift": drift, "tol": tol, "within_tol": drift <= tol,
            "rounds": rounds}


def _measure_drift_named(wname: str, *, arch: str, mesh: str, policy: str,
                         smoke: bool, rounds: int) -> dict:
    """measure_drift for the wire named `wname` on the debug mesh `mesh`."""
    from repro.configs import registry as R
    _, quantize, swire = next(x for x in WIRES if x[0] == wname)
    cfg = R.get_smoke_config(arch) if smoke else R.get_config(arch)
    pods, n_data, n_model = _mesh_tuple(mesh)
    jmesh = make_debug_mesh(n_data, n_model, pods=pods)
    run_cfg = RunConfig(sharding=policy, sync_quantize=quantize,
                        sync_wire=swire, schedule="constant", h_base=4,
                        total_steps=10 ** 6, remat=False)
    return measure_drift(cfg, run_cfg, jmesh, policy, rounds=rounds)


def measure_drift_guarded(wname: str, *, arch: str, mesh: str, policy: str,
                          smoke: bool = True, rounds: int = 3,
                          timeout: float = 300.0, attempts: int = 3) -> dict:
    """measure_drift in a watchdog subprocess (`--drift-worker` mode).

    XLA's in-process CPU collective rendezvous can — rarely, and
    scheduling-dependently — deadlock when n_devices simulated devices
    contend for few cores: one participant's execution thread never gets
    scheduled while every other rank waits forever at the rendezvous.  The
    race cannot be closed from client code, so the guard is containment:
    run the measurement in a fresh process, kill it past `timeout`, retry.
    A healthy measurement takes well under a minute at smoke scale.

    The race is the CPU backend's: on an accelerator the measurement runs
    in this process, which holds the device a child could not open."""
    if jax.default_backend() != "cpu":
        return _measure_drift_named(wname, arch=arch, mesh=mesh,
                                    policy=policy, smoke=smoke,
                                    rounds=rounds)
    import subprocess
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "repro.launch.autotune",
           "--drift-worker", wname, "--arch", arch, "--mesh", mesh,
           "--policy", policy, "--drift-rounds", str(rounds)]
    if not smoke:
        cmd.append("--full")
    last = ""
    for attempt in range(1, attempts + 1):
        try:
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=timeout, env=env)
        except subprocess.TimeoutExpired:
            last = f"attempt {attempt}: hung past {timeout:.0f}s (killed)"
            print(f"[autotune] drift worker {last}; retrying",
                  file=sys.stderr)
            continue
        if out.returncode == 0:
            return json.loads(out.stdout)
        last = f"attempt {attempt}: rc={out.returncode}: {out.stderr[-2000:]}"
        print(f"[autotune] drift worker failed; retrying\n{last}",
              file=sys.stderr)
    raise RuntimeError(
        f"drift measurement for wire={wname} failed after {attempts} "
        f"attempts: {last}")


def time_plan(cfg, run_cfg, mesh, policy: str, *, sync: str, depth: int,
              b_loc: int = 2, seq: int = 32, warmup: int = 1,
              rounds: int = 3, seed: int = 0) -> dict:
    """Wall-clock full engine rounds (h local steps + the plan's sync) on
    the mesh — the timing harness benchmarks/table4_walltime.py uses, with
    the state living on the real device mesh."""
    from repro.core import schedules
    from repro.core.engine import RoundEngine
    from repro.models import param as pm
    from repro.optim.lr import make_lr_fn

    w = pm.worker_count(policy, mesh)
    eng = RoundEngine(cfg, run_cfg, workers=w, b_loc=b_loc, seq=seq,
                      seed=seed, data="device", layout="flat_sharded",
                      sync=sync, overlap_depth=depth, mesh=mesh,
                      policy=policy)
    lr_fn = make_lr_fn(run_cfg)
    state = eng.init_state()
    t = 0
    # warmup compiles every round-program variant incl. the flush/apply, so
    # the timed window holds only steady-state rounds (table4_walltime's
    # protocol)
    for _ in range(warmup):
        h = schedules.get_h(run_cfg, t, lr_fn)
        state, _ = eng.run_round(state, t, h, lr_fn)
        t += h
    state = eng.flush(state)
    jax.block_until_ready(jax.tree.leaves(state))
    t0 = time.perf_counter()
    for _ in range(rounds):
        h = schedules.get_h(run_cfg, t, lr_fn)
        state, _ = eng.run_round(state, t, h, lr_fn)
        t += h
    jax.block_until_ready(jax.tree.leaves(state))
    dt = time.perf_counter() - t0
    eng.flush(state)
    return {"s_per_round": dt / rounds, "rounds": rounds,
            "h": run_cfg.h_base}


def autotune(arch: str = "starcoder2-3b", *, mesh: str = "4x2",
             policy: str = "dp", smoke: bool = True, drift_rounds: int = 3,
             time_rounds: int = 3, skip_timing: bool = False,
             verbose: bool = True) -> dict:
    """Enumerate, measure, choose.  Returns the BENCH_sync record."""
    from repro.configs import registry as R
    from repro.models import param as pm

    cfg = R.get_smoke_config(arch) if smoke else R.get_config(arch)
    pods, n_data, n_model = _mesh_tuple(mesh)
    jmesh = make_debug_mesh(n_data, n_model, pods=pods)
    w = pm.worker_count(policy, jmesh)

    def rc(quantize, wire, h=4, steps=10 ** 6):
        return RunConfig(sharding=policy, sync_quantize=quantize,
                         sync_wire=wire, schedule="constant", h_base=h,
                         total_steps=steps, remat=False)

    log = (lambda *a: print(*a, file=sys.stderr)) if verbose else \
        (lambda *a: None)
    wires, plans = {}, []
    for wname, quantize, swire in WIRES:
        log(f"[autotune] lowering wire={wname}")
        wrec = lower_wire(cfg, rc(quantize, swire), jmesh, policy)
        log(f"[autotune] drift wire={wname}")
        wrec["drift"] = measure_drift_guarded(wname, arch=arch, mesh=mesh,
                                              policy=policy, smoke=smoke,
                                              rounds=drift_rounds)
        wrec["wire_dtype"] = _wire_dtype_name(wname, w)
        wires[wname] = wrec
        for sync, depth in SYNCS:
            plan = {"plan": f"{wname}+{sync}{depth}", "wire": wname,
                    "sync": sync, "overlap_depth": depth,
                    "quantize": quantize, "sync_wire": swire,
                    "wire_dtype": wrec["wire_dtype"],
                    "bytes_on_wire": wrec["bytes_on_wire"],
                    "payload_bytes_by_dtype": wrec["payload_bytes_by_dtype"],
                    "drift": wrec["drift"]["drift"],
                    "drift_tol": wrec["drift"]["tol"],
                    "drift_ok": wrec["drift"]["within_tol"]}
            if not skip_timing:
                log(f"[autotune] timing plan={plan['plan']}")
                plan.update(time_plan(cfg, rc(quantize, swire), jmesh,
                                      policy, sync=sync, depth=depth,
                                      rounds=time_rounds))
            plans.append(plan)

    eligible = [p for p in plans if p["drift_ok"]]
    key = lambda p: (p["bytes_on_wire"], p.get("s_per_round", 0.0))
    chosen = min(eligible or plans, key=key)
    ref = next(p for p in plans if p["plan"] == "f32+blocking0")
    rec = {
        "schema": SCHEMA, "arch": arch, "smoke": smoke, "mesh": mesh,
        "policy": policy, "layout": "flat_sharded", "workers": w,
        "n_devices": jmesh.devices.size,
        "plans": plans,
        "wires": {k: {kk: vv for kk, vv in v.items() if kk != "drift"}
                  for k, v in wires.items()},
        "chosen": chosen["plan"],
        "chosen_bytes_on_wire": chosen["bytes_on_wire"],
        "chosen_drift": chosen["drift"],
        "reference_plan": ref["plan"],
        "ring_vs_auto_bytes_ratio": (
            wires["int-codes"]["bytes_on_wire"]
            / max(wires["ring-int8"]["bytes_on_wire"], 1)),
    }
    if not skip_timing:
        rec["chosen_s_per_round"] = chosen["s_per_round"]
        rec["speed_ratio_chosen_vs_reference"] = (
            chosen["s_per_round"] / ref["s_per_round"])
    return rec


def gate(rec: dict, baseline: dict, *, regress_frac: float = 0.10) -> list:
    """Compare a fresh trajectory point against the committed baseline.
    Returns the list of violations (empty = pass).  Speed gates on the
    chosen/reference RATIO, never absolute seconds — CI machines vary;
    their ratio between two plans timed in the same run does not."""
    fails = []
    if rec["chosen_bytes_on_wire"] > baseline["chosen_bytes_on_wire"]:
        fails.append(
            f"bytes-on-wire grew: {rec['chosen_bytes_on_wire']} > baseline "
            f"{baseline['chosen_bytes_on_wire']}")
    if rec["ring_vs_auto_bytes_ratio"] < 2.0:
        fails.append(
            "ring byte reduction fell below the 2x acceptance floor: "
            f"{rec['ring_vs_auto_bytes_ratio']:.2f}x")
    r, b = (rec.get("speed_ratio_chosen_vs_reference"),
            baseline.get("speed_ratio_chosen_vs_reference"))
    if r is not None and b is not None and r > b * (1.0 + regress_frac):
        fails.append(
            f"s/round ratio regressed >{regress_frac:.0%}: {r:.3f} vs "
            f"baseline {b:.3f} (chosen plan vs in-run f32+blocking)")
    if not rec["plans"]:
        fails.append("no plans measured")
    return fails


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--full", action="store_true",
                    help="production config (default: smoke, CPU-runnable)")
    ap.add_argument("--mesh", default="4x2",
                    help="debug mesh data x model or pod x data x model")
    ap.add_argument("--policy", default="dp", choices=["dp", "fsdp"])
    ap.add_argument("--drift-rounds", type=int, default=3)
    ap.add_argument("--time-rounds", type=int, default=3)
    ap.add_argument("--skip-timing", action="store_true",
                    help="lowering + drift only (fast smoke of the "
                         "enumeration; the record then carries no s/round "
                         "and the speed gate is skipped)")
    ap.add_argument("--out", default=None,
                    help="write the BENCH_sync.json record here")
    ap.add_argument("--append", default=None,
                    help="append this run's record as a point to a "
                         "trajectory file (schema bench_sync_trajectory/v1; "
                         "created if missing, a bare bench_sync/v1 record "
                         "is promoted to a one-point trajectory)")
    ap.add_argument("--baseline", default=None,
                    help="gate this run against a committed baseline "
                         "record; non-zero exit on violation")
    ap.add_argument("--update-baseline", action="store_true",
                    help="write the fresh record over --baseline instead "
                         "of gating")
    ap.add_argument("--regress-frac", type=float, default=0.10)
    # internal: measure_drift_guarded's watchdog child — measure one wire's
    # drift and print the JSON record on stdout
    ap.add_argument("--drift-worker", default=None, choices=[w[0]
                    for w in WIRES], help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.drift_worker:
        print(json.dumps(_measure_drift_named(
            args.drift_worker, arch=args.arch, mesh=args.mesh,
            policy=args.policy, smoke=not args.full,
            rounds=args.drift_rounds)))
        return

    rec = autotune(args.arch, mesh=args.mesh, policy=args.policy,
                   smoke=not args.full, drift_rounds=args.drift_rounds,
                   time_rounds=args.time_rounds,
                   skip_timing=args.skip_timing)
    text = json.dumps(rec, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    if args.append:
        traj = {"schema": TRAJECTORY_SCHEMA, "points": []}
        if os.path.exists(args.append):
            with open(args.append) as f:
                prev = json.load(f)
            if prev.get("schema") == TRAJECTORY_SCHEMA:
                traj = prev
            elif prev.get("schema") == SCHEMA:
                traj["points"].append(prev)
        traj["points"].append(rec)
        with open(args.append, "w") as f:
            json.dump(traj, f, indent=1)
        print(f"trajectory: {len(traj['points'])} points -> {args.append}",
              file=sys.stderr)
    print(text)
    if args.baseline and args.update_baseline:
        with open(args.baseline, "w") as f:
            f.write(text)
        print(f"baseline updated: {args.baseline}", file=sys.stderr)
    elif args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)
        fails = gate(rec, base, regress_frac=args.regress_frac)
        for msg in fails:
            print(f"GATE FAIL: {msg}", file=sys.stderr)
        if fails:
            raise SystemExit(1)
        print("perf gate: PASS (vs baseline "
              f"{base.get('chosen', '?')}, bytes "
              f"{base.get('chosen_bytes_on_wire', '?')})", file=sys.stderr)


if __name__ == "__main__":
    main()
