"""Multi-host process bootstrap + a REAL multi-process execution path.

Two jobs:

1. Production bootstrap (TPU pods).  On real TPU v5e, each host owns 4
   chips; a 16x16 pod is 64 hosts and the 2-pod job is 128.  `initialize()`
   wires `jax.distributed`, then `make_production_mesh()` (launch/mesh.py)
   builds the global mesh over `jax.devices()` exactly as the dry-run does
   over placeholder devices — the same `train_round` / `serve_step` programs
   run unchanged.

2. CPU multi-process execution (the thing this module can actually *run*
   anywhere): `run()` executes the sharded sync — and full RoundEngine
   rounds — across N real `jax.distributed` CPU processes with gloo
   collectives.  Every process holds 1/N of the devices of the same global
   mesh the single-process debug runs use; the explicit reduce_scatter /
   all_gather legs of the flat_sharded sync (core/sync.py) then cross true
   process boundaries.  Quantized sync is asserted BITWISE against the
   process-local host path: the worker mean runs over integer codes, so no
   collective ordering — in-process XLA or gloo — can change a bit.  The
   pytest harness (tests/test_multihost.py) spawns the processes and
   additionally checks the multi-process digests against a single-process
   8-simulated-device run of this same module.

   `--wire ring-int8` swaps the one-shot reduce_scatter for the W-hop
   re-quantizing int8 ppermute ring (core/sync.py §ring).  The ring is
   deliberately beyond-exact: per-hop requantization makes the mesh path
   differ from the host reference (and, at the engine's overlap seam, XLA's
   refusion across the program boundary can flip a requant code), so ring
   runs are asserted within `ring_tolerance` — never bitwise.  The shard
   hashes stay exact across PROCESS SPLITS though: the ring has no
   cross-device reductions at all (each hop's arithmetic is device-local and
   ppermute moves int8 bytes verbatim), so a 1-process and an N-process run
   of the same mesh still hash identically shard for shard.

3. Elastic fault tolerance (README §Elastic training): `--chaos` drives a
   fault-injection controller across worker GENERATIONS.  `jax.distributed`
   cannot resize a live process group — a dead gloo member deadlocks every
   collective — so each worker set is one OS-process generation (one engine
   MembershipEpoch), and the manifest checkpoint (checkpoint/io.py
   save_sharded) is the currency between generations.  Inside a generation,
   workers run `--sync partial` engine rounds in lockstep, exchanging
   heartbeat files at every round boundary BEFORE entering the round's
   collectives; a worker that died cannot announce, so the survivors detect
   the loss with a bounded timeout instead of deadlocking, exit with a
   membership verdict (rc 3), and the controller respawns the surviving
   lanes from the last round-boundary manifest:

     --chaos kill:worker=2,round=1   kill 1 of 4 mid-run; survivors redo
                                     the round on the reduced mesh, proven
                                     BITWISE (integer-code domain) against
                                     a single-process 3-worker reference
     --chaos preempt-restore         ...then rejoin the worker from the
                                     manifest checkpoint (restore under a
                                     different process count; the rejoined
                                     lane re-anchors to consensus) and
                                     prove the 4-worker continuation
                                     bitwise the same way

Spawn it yourself (the multihost CPU runbook, README §Multihost):

  PYTHONPATH=src python -m repro.launch.multihost \
      --spawn 2 --total-devices 8 --mesh 2x2x2 --policy fsdp --quantize

Worker environment (set by --spawn, or by you):
  REPRO_COORDINATOR   host:port of process 0
  REPRO_NUM_PROCESSES total process count
  REPRO_PROCESS_ID    this process's index

NOTE: jax is imported lazily everywhere in this module so `main()` can pin
the per-process simulated-device count (XLA_FLAGS) before jax initializes.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import socket
import subprocess
import sys
import tempfile
import time

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


class TopologyError(RuntimeError):
    """The device topology does not match the requested production mesh."""


def initialize(*, retries: int = 3, backoff: float = 0.5) -> bool:
    """Wire `jax.distributed` from the REPRO_* environment; no-op (returns
    False) when REPRO_COORDINATOR is unset (single-process dev / dry-run).
    On the CPU backend, cross-process collectives need the gloo
    implementation — selected here; the option is scoped to the CPU client,
    so setting it is harmless on TPU.

    Bounded retry + exponential backoff: the coordinator bind races with
    spawn order (a worker can dial before process 0 is listening, or the
    probed port can be lost to another server between probe and bind), and
    both surface as an initialize() failure that a short backoff resolves.
    After `retries` failures the last error propagates — an elastic
    controller treats that worker as never having joined the epoch."""
    coord = os.environ.get("REPRO_COORDINATOR")
    if not coord:
        return False
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    last = None
    for attempt in range(max(1, retries)):
        try:
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=int(os.environ["REPRO_NUM_PROCESSES"]),
                process_id=int(os.environ["REPRO_PROCESS_ID"]),
            )
            return True
        except Exception as e:   # noqa: BLE001 — retrying the whole wire-up
            last = e
            if attempt + 1 < retries:
                time.sleep(backoff * (2 ** attempt))
    raise last


def runtime_info() -> dict:
    import jax
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
        "platform": jax.devices()[0].platform,
    }


def assert_production_topology(*, multi_pod: bool) -> None:
    """Raise TopologyError unless the device count matches the production
    mesh.  A real exception, not `assert`: launch scripts run under
    `python -O`, which strips asserts — a silently wrong topology would
    train on a misshapen mesh."""
    import jax
    want = 512 if multi_pod else 256
    got = len(jax.devices())
    if got != want:
        raise TopologyError(
            f"expected {want} chips for the "
            f"{'2x16x16' if multi_pod else '16x16'} mesh, found {got}")


# --------------------------------------------------------------------------
# The executable path: sharded sync / engine rounds across real processes
# --------------------------------------------------------------------------

def _parse_mesh(mesh: str):
    dims = tuple(int(x) for x in mesh.split("x"))
    axes = ("pod", "data", "model") if len(dims) == 3 else ("data", "model")
    return dims, axes


def _demo_params(seed: int = 0):
    """A small mixed-dtype params pytree for the sync harness: two dtype
    buckets, sizes chosen so the W*S chunking actually pads."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    return {
        "w_in": mk(13, 24), "w_attn": mk(24, 24), "bias": mk(17),
        "w_out": mk(24, 13), "gate": mk(3, 5, 7),
        "h_bf16": mk(9, 11).astype(jnp.bfloat16),
        "e_bf16": mk(21).astype(jnp.bfloat16),
    }


def _digest(arrays) -> str:
    import numpy as np
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


def _shard_hashes(tag: str, arr) -> dict:
    """{f"{tag}|{global index}": sha1(bytes)} over this process's shards —
    the cross-run comparison unit: a 1-process and an N-process run of the
    same program must produce identical hashes shard for shard."""
    import numpy as np
    out = {}
    for s in arr.addressable_shards:
        key = f"{tag}|{[(sl.start, sl.stop) for sl in s.index]}"
        out[key] = hashlib.sha1(
            np.ascontiguousarray(np.asarray(s.data)).tobytes()).hexdigest()
    return out


def run_sync(*, mesh: str = "2x2x2", policy: str = "fsdp",
             quantize: bool = True, momentum: float = 0.0,
             overlap: bool = False, rounds: int = 3, seed: int = 0,
             wire: str = "auto", membership: str = "") -> dict:
    """Execute `rounds` sharded syncs on the global mesh — across however
    many processes own its devices — and assert every addressable shard
    bitwise-equal to the process-local host-path reference (the mesh-less
    flat sync every test in tests/ anchors to).

    Each round perturbs worker params with seeded host noise (identical on
    every process) and syncs.  With `overlap`, the reduce (begin) is issued
    at the round boundary and the gather (apply) deferred to the next round
    — the RS leg's pending int16 code-sums then live across a program
    boundary, exactly the engine's `--sync overlap` seam.

    Bitwise holds for any mesh when `quantize` (integer-code mean) and for
    2-worker meshes unquantized (a single f32 addition has one order);
    callers pick configurations accordingly (tests/test_multihost.py).
    wire="ring-int8" relaxes the contract: the mesh ring and the host ring
    fold identical math through different XLA programs, so requant codes can
    flip — shards must land within `ring_tolerance` of the reference
    instead (the module docstring's beyond-exact semantics).

    `membership` ("1,1,0,1") switches both paths to the PARTIAL sync
    (core/sync.py §Partial participation): the mesh psum runs over all W
    lanes but masked deltas are zeroed pre-quantizer and the mean divides
    by |P| — asserted bitwise against the host partial reference, and
    (quantized) against a W'=|P| run over just the participant rows: the
    integer-code-domain exactness the elastic path rests on.  Partial
    composes with neither overlap (the pending would cross a membership
    boundary) nor the ring wire (W is baked into every hop)."""
    import jax
    from repro.launch.mesh import make_mesh
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import RunConfig
    from repro.core import flat as F
    from repro.core.sync import (make_sync, make_sync_apply, make_sync_begin,
                                 make_sync_partial, ring_tolerance)
    from repro.models import param as pm

    dims, axes = _parse_mesh(mesh)
    jmesh = make_mesh(dims, axes)
    if membership and (overlap or wire == "ring-int8"):
        raise ValueError("--membership composes with neither --overlap nor "
                         "the ring wire (run_sync docstring)")
    run_cfg = RunConfig(sharding=policy, sync_quantize=quantize,
                        outer_momentum=momentum, sync_wire=wire)
    w = pm.worker_count(policy, jmesh)
    waxes = pm.worker_mesh_axes(policy, jmesh)
    saxes = tuple(a for a in jmesh.axis_names if a not in waxes)
    sizes = pm.mesh_axis_sizes(jmesh)
    shards = int(np.prod([sizes[a] for a in waxes + saxes]))

    params = _demo_params(seed)
    spec_m = F.ShardedFlatSpace(params, shards, mesh=jmesh,
                                worker_axes=waxes, shard_axes=saxes)
    spec_h = F.ShardedFlatSpace(params, shards)

    stacked = {k: jnp.broadcast_to(v[None], (w,) + v.shape)
               for k, v in params.items()}
    base = {"params": spec_h.flatten(stacked, lead=1)}
    if quantize or momentum > 0.0:
        base["anchor"] = spec_h.flatten(params)
    if momentum > 0.0:
        base["outer_mu"] = {b: jnp.zeros(spec_h.buffer_size(b), jnp.float32)
                            for b in spec_h.buckets}

    sspec = F.flat_state_specs(run_cfg, waxes, spec_m)
    put = lambda x, ps: F.make_global(x, jmesh, ps)

    st_m = {k: {b: put(v[b], sspec[k][b]) for b in v}
            for k, v in base.items()}
    st_h = dict(base)

    rng = np.random.RandomState(seed + 1)
    noises = [{k: (rng.randn(w, *v.shape) * 0.01).astype(np.float32)
               for k, v in params.items()} for _ in range(rounds)]

    def steps(state, spec, noise_bufs_put):
        return dict(state, params={
            b: state["params"][b] + noise_bufs_put[b].astype(
                state["params"][b].dtype)
            for b in state["params"]})

    mask = (np.asarray([float(x) for x in membership.split(",")], np.float32)
            if membership else None)
    if mask is not None and mask.shape != (w,):
        raise ValueError(f"--membership needs {w} entries, got {membership!r}")

    if overlap:
        begin_m = jax.jit(make_sync_begin(run_cfg, spec_m))
        apply_m = jax.jit(make_sync_apply(run_cfg, spec_m))
        begin_h = jax.jit(make_sync_begin(run_cfg, spec_h))
        apply_h = jax.jit(make_sync_apply(run_cfg, spec_h))
    elif mask is not None:
        part_m = jax.jit(make_sync_partial(run_cfg, spec_m))
        part_h = jax.jit(make_sync_partial(run_cfg, spec_h))
        sync_m = lambda st: part_m(st, jnp.asarray(mask))
        sync_h = lambda st: part_h(st, jnp.asarray(mask))
    else:
        sync_m = jax.jit(make_sync(run_cfg, spec_m))
        sync_h = jax.jit(make_sync(run_cfg, spec_h))

    pend_m = pend_h = None
    for noise in noises:
        nb = spec_h.flatten(
            {k: jnp.asarray(v) for k, v in noise.items()}, lead=1)
        nb_put = {b: put(nb[b], sspec["params"][b]) for b in nb}
        if overlap:
            if pend_m is not None:
                st_m = apply_m(st_m, pend_m)
                st_h = apply_h(st_h, pend_h)
            st_m, st_h = steps(st_m, spec_m, nb_put), steps(st_h, spec_h, nb)
            pend_m, pend_h = begin_m(st_m), begin_h(st_h)
        else:
            st_m, st_h = steps(st_m, spec_m, nb_put), steps(st_h, spec_h, nb)
            st_m, st_h = sync_m(st_m), sync_h(st_h)
    if overlap and pend_m is not None:
        st_m, st_h = apply_m(st_m, pend_m), apply_h(st_h, pend_h)

    # partial + quantized: the consensus must ALSO equal a W'=|P| run over
    # just the participant rows — Σ_{i∈P} q_i / |P| is the same integer sum
    # whether the absent lanes contribute zero codes or don't exist (the
    # integer-code-domain exactness claim; f32 sums reassociate, so the
    # unquantized form is covered by the mesh==host assert above only)
    participant_exact = None
    if mask is not None and quantize:
        rows = [i for i in range(w) if mask[i]]
        wp = len(rows)
        spec_p = F.ShardedFlatSpace(_demo_params(seed), wp)
        stacked_p = {k: jnp.stack([v] * wp) for k, v in params.items()}
        st_p = {"params": spec_p.flatten(stacked_p, lead=1),
                "anchor": spec_p.flatten(params)}
        if momentum > 0.0:
            st_p["outer_mu"] = {b: jnp.zeros(spec_p.buffer_size(b),
                                             jnp.float32)
                                for b in spec_p.buckets}
        part_p = jax.jit(make_sync_partial(run_cfg, spec_p))
        ones = jnp.ones(wp, jnp.float32)
        for noise in noises:
            nz = {k: jnp.asarray(v[rows]) for k, v in noise.items()}
            nb = spec_p.flatten(nz, lead=1)
            st_p = dict(st_p, params={
                b: st_p["params"][b] + nb[b].astype(st_p["params"][b].dtype)
                for b in st_p["params"]})
            st_p = part_p(st_p, ones)
        full = spec_h.unflatten(st_h["params"], lead=1)
        part = spec_p.unflatten(st_p["params"], lead=1)
        participant_exact = all(
            bool(jnp.all(full[k][0] == part[k][0])) for k in full)

    # every addressable shard of the distributed state must equal the
    # corresponding slice of the (fully-replicated) host reference.  For the
    # ring wire the comparison is tolerance-based AFTER a per-element cast
    # allowance |ref|*eps(dtype)*rounds: each round's anchor cast can put
    # the two paths one output-dtype quantum apart (a straddled bf16
    # rounding boundary), and that divergence re-enters the next round's
    # delta — up to one quantum PER ROUND on bf16 buckets.
    max_diff, excess, hashes = 0.0, 0.0, {}
    for k in sorted(st_h):
        for b in sorted(st_h[k]):
            ref = np.asarray(st_h[k][b], np.float32)
            eps = (2.0 ** -7 if "bfloat16" in str(st_h[k][b].dtype)
                   else 2.0 ** -23) * rounds
            for s in st_m[k][b].addressable_shards:
                got = np.asarray(s.data, np.float32)
                if got.size:
                    d = np.abs(got - ref[s.index])
                    max_diff = max(max_diff, float(np.max(d)))
                    excess = max(excess, float(
                        np.max(d - np.abs(ref[s.index]) * eps)))
            hashes.update(_shard_hashes(f"{k}/{b}", st_m[k][b]))

    info = runtime_info()
    if wire == "ring-int8":
        # every round's delta-from-anchor is exactly that round's noise
        # (post-sync params == anchor), so the noise amax bounds the ring's
        # per-round requantization error
        amax_d = max(float(np.max(np.abs(v)))
                     for nz in noises for v in nz.values())
        tol = ring_tolerance(w, amax_d, rounds)
        ok = excess <= tol
    else:
        tol = 0.0
        ok = max_diff == 0.0 and participant_exact is not False
    # the digest is over the host reference — meaningful ONLY because the
    # shard assertions above tie the distributed state to it (bitwise, or
    # within ring_tolerance for the ring wire), so gate it on `ok`: a broken
    # distributed path can never produce a matching digest
    digest = (_digest([st_h[k][b] for k in sorted(st_h)
                       for b in sorted(st_h[k])])
              if ok else f"MISMATCH:{max_diff:.3e}")
    return {
        "mode": "sync", "ok": ok, "max_abs_diff": max_diff,
        "digest": digest,
        "shard_hashes": hashes,
        "mesh": mesh, "policy": policy, "workers": w, "shards": shards,
        "quantize": quantize, "momentum": momentum, "overlap": overlap,
        "membership": membership, "participant_exact": participant_exact,
        "rounds": rounds, "wire": wire, "ring_tol": tol,
        "wire_dtype": ("int8" if wire == "ring-int8" else
                       "int16" if quantize and w * 127 < 2 ** 15 else
                       "int32" if quantize else "float32"),
        **info,
    }


def run_engine(*, mesh: str = "2x2x2", policy: str = "fsdp",
               quantize: bool = True, momentum: float = 0.0,
               rounds: int = 2, seed: int = 0,
               arch: str = "starcoder2-3b", sync: str = "blocking",
               overlap_depth: int = 0, wire: str = "auto") -> dict:
    """Execute full RoundEngine communication rounds (local steps + sharded
    sync) on the global mesh, across real process boundaries: the engine is
    built exactly as single-process — same config, same mesh axes — with
    `mesh=` handed through so init lays global arrays onto it.

    Cross-process invariant: the round program is SPMD, so every process
    must observe the identical replicated loss scalar, and a 1-process run
    of the same mesh produces bitwise-identical state shards when the sync
    is quantized (the only cross-worker reduction in a dp/fsdp round whose
    result feeds back into the state; integer codes make it
    order-independent).

    sync="overlap": the round programs thread the pending reduce across
    their boundaries (engine `--sync overlap` — `make_sync_begin` at each
    round's end, the gather/apply inside the next program), with the
    pending's worker-sharded payload living on the distributed devices
    between programs.  A blocking engine runs the same trajectory alongside
    as the in-process reference; at depth 0 the flushed overlap state must
    match it BITWISE, shard for shard, on any mesh/process split (identical
    op sequence, deterministic collectives — tests/test_sharded.py proves
    the host edition).  Depth > 0 is the correction form: finite and close,
    reported but not asserted bitwise.

    wire="ring-int8" weakens the depth-0 contract to tolerance: splitting
    begin/apply across the program boundary changes how XLA fuses the ring's
    f32 hop arithmetic, and a reassociated rounding can flip a requant code
    — one quantization level, bounded per round by `ring_tolerance` of the
    (h·lr)-bounded local-step delta."""
    import jax
    from repro.launch.mesh import make_mesh
    import numpy as np

    from repro.configs import registry as R
    from repro.configs.base import RunConfig
    from repro.core import schedules
    from repro.core.engine import RoundEngine
    from repro.core.sync import ring_tolerance
    from repro.optim.lr import make_lr_fn
    from repro.models import param as pm

    dims, axes = _parse_mesh(mesh)
    jmesh = make_mesh(dims, axes)
    cfg = R.get_smoke_config(arch)
    run_cfg = RunConfig(schedule="qsr", optimizer="adamw",
                        total_steps=2 * rounds, peak_lr=3e-3, end_lr=1e-6,
                        warmup_steps=1, h_base=2, alpha=0.001, remat=False,
                        weight_decay=0.01, sync_quantize=quantize,
                        outer_momentum=momentum, sharding=policy,
                        sync_wire=wire)
    w = pm.worker_count(policy, jmesh)
    mk = lambda s, d: RoundEngine(cfg, run_cfg, workers=w, b_loc=2, seq=16,
                                  seed=seed, data="device",
                                  layout="flat_sharded", sync=s,
                                  overlap_depth=d, mesh=jmesh, policy=policy)
    eng = mk(sync, overlap_depth)
    ref = mk("blocking", 0) if sync == "overlap" else None
    lr_fn = make_lr_fn(run_cfg)
    state = eng.init_state()
    ref_state = ref.init_state() if ref else None
    losses, ref_losses = [], []
    tol = 0.0
    for t, h in schedules.rounds(run_cfg, lr_fn):
        state, m = eng.run_round(state, t, h, lr_fn)
        losses.append(float(m["loss"]))
        if wire == "ring-int8":
            # per-round delta amax bound: h AdamW steps of normalized-update
            # magnitude <= ~lr each, x4 headroom for bias-corrected early
            # steps + weight decay — feeds the per-round requant error bound
            tol += ring_tolerance(w, 4.0 * h * run_cfg.peak_lr, 1)
        if ref:
            ref_state, mr = ref.run_round(ref_state, t, h, lr_fn)
            ref_losses.append(float(mr["loss"]))
    state = eng.flush(state)

    def hash_state(st, tag=""):
        out = {}
        for k in ("params", "anchor"):
            if k in st:
                for b, arr in st[k].items():
                    out.update(_shard_hashes(f"{tag}{k}/{b}", arr))
        return out

    hashes = hash_state(state)
    ok = all(np.isfinite(losses))
    rec = {}
    if ref:
        max_diff, excess = 0.0, 0.0
        for k in ("params", "anchor"):
            if k in state:
                for b in state[k]:
                    eps = (2.0 ** -7 if "bfloat16" in str(state[k][b].dtype)
                           else 2.0 ** -23) * max(len(losses), 1)
                    for s, r in zip(state[k][b].addressable_shards,
                                    ref_state[k][b].addressable_shards):
                        a = np.asarray(s.data, np.float32)
                        bb = np.asarray(r.data, np.float32)
                        if a.size:
                            d = np.abs(a - bb)
                            max_diff = max(max_diff, float(np.max(d)))
                            # ring: allow one output-dtype quantum PER ROUND
                            # (straddled rounding boundaries re-enter the
                            # next round's delta) before testing the bound
                            excess = max(excess, float(
                                np.max(d - np.abs(bb) * eps)))
        matches = (excess <= tol if wire == "ring-int8"
                   else max_diff == 0.0)
        if overlap_depth == 0:
            ok = ok and matches
        rec = {"blocking_losses": ref_losses,
               "overlap_matches_blocking": matches,
               "max_abs_diff_vs_blocking": max_diff,
               "wire_tolerance": tol}
    info = runtime_info()
    return {
        "mode": "engine", "ok": ok, "losses": losses,
        "shard_hashes": hashes, "mesh": mesh, "policy": policy, "workers": w,
        "quantize": quantize, "momentum": momentum, "rounds": len(losses),
        "sync": sync, "overlap_depth": overlap_depth, "wire": wire,
        "arch": arch, **rec, **info,
    }


def probe() -> dict:
    """Cheapest possible cross-process collective: one psum over all
    devices.  tests/test_multihost.py runs this first and skips gracefully
    when the distributed CPU backend is unavailable."""
    import jax
    from repro.launch.mesh import make_mesh
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = len(jax.devices())
    jmesh = make_mesh((n,), ("x",))
    host = np.arange(n, dtype=np.float32)
    arr = jax.make_array_from_callback(
        (n,), NamedSharding(jmesh, P("x")), lambda idx: host[idx])
    total = float(jax.jit(jnp.sum)(arr))
    return {"mode": "probe", "ok": total == n * (n - 1) / 2,
            "devices": n, **runtime_info()}


# --------------------------------------------------------------------------
# Elastic fault tolerance (module docstring §3, README §Elastic training)
# --------------------------------------------------------------------------

class Heartbeat:
    """File-based liveness detector for lockstep round workers.

    Entering round r, every worker `announce(r)`s a heartbeat file, then
    `await_peers(r)` polls for all peers' files under a bounded timeout.
    A dead worker cannot announce, so the survivors learn of the loss
    BEFORE entering the round's collectives — the only safe moment: one
    dead gloo member deadlocks every collective, and there is no timeout
    inside them.  Workers are in lockstep (the previous round ended in a
    collective barrier), so a missing heartbeat after `timeout` means
    dead-or-hopelessly-straggling either way; the verdict is the same —
    leave the epoch and let the controller respawn the survivors."""

    def __init__(self, path: str, pid: int, nprocs: int, *,
                 timeout: float = 30.0, poll: float = 0.05):
        self.path, self.pid, self.n = path, pid, nprocs
        self.timeout, self.poll = timeout, poll
        os.makedirs(path, exist_ok=True)

    def _f(self, rnd: int, pid: int) -> str:
        return os.path.join(self.path, f"hb-{rnd:06d}-{pid:05d}")

    def announce(self, rnd: int) -> None:
        with open(self._f(rnd, self.pid), "w") as f:
            f.write(f"{time.time()}")

    def await_peers(self, rnd: int) -> list[int]:
        """Block until every peer announced round `rnd` or the timeout
        lapses; returns the pids still missing (empty = proceed)."""
        deadline = time.monotonic() + self.timeout
        missing = [p for p in range(self.n) if p != self.pid]
        while missing and time.monotonic() < deadline:
            missing = [p for p in missing
                       if not os.path.exists(self._f(rnd, p))]
            if missing:
                time.sleep(self.poll)
        return [p for p in missing if not os.path.exists(self._f(rnd, p))]


def _device_barrier() -> None:
    """Cross-process barrier for checkpoint manifests (all shard files
    durable before process 0 names them)."""
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("repro-manifest")


def _parse_chaos(spec: str):
    """'kill:worker=2,round=1' -> ('kill', {'worker': 2, 'round': 1})."""
    if not spec:
        return None, {}
    kind, _, rest = spec.partition(":")
    kv = {}
    for part in rest.split(","):
        if part:
            a, _, b = part.partition("=")
            kv[a.strip()] = int(b)
    return kind, kv


def _elastic_hashes(state) -> dict:
    """Shard hashes over the FULL flat state — params, anchor, AND the
    per-lane Adam moments / outer momentum: a restore or trajectory
    mismatch hiding in the moments would otherwise surface only as a
    slow parameter drift rounds later."""
    out = {}
    for tag, arr in _elastic_state_arrays(state):
        out.update(_shard_hashes(tag, arr))
    return out


def _elastic_state_arrays(state):
    for k in ("params", "anchor", "outer_mu"):
        if k in state:
            for b, arr in state[k].items():
                yield f"{k}/{b}", arr
    for k in ("m", "v", "mu"):
        for b, arr in (state.get("opt") or {}).get(k, {}).items():
            yield f"opt.{k}/{b}", arr


def _elastic_norms(state) -> dict:
    """{shard key: [l2, absmax]} in float64 over the same shard units as
    `_elastic_hashes` — the TOLERANCE comparison for legs where bitwise is
    not contractual (a regrown worker set compiles a different per-process
    XLA program, whose lane-local f32 math can drift by ulps across
    process layouts even though the sync itself stays integer-exact)."""
    import numpy as np
    out = {}
    for tag, arr in _elastic_state_arrays(state):
        for s in arr.addressable_shards:
            key = f"{tag}|{[(sl.start, sl.stop) for sl in s.index]}"
            x = np.asarray(s.data, dtype=np.float64)
            out[key] = [float(np.sqrt(np.sum(x * x))),
                        float(np.max(np.abs(x))) if x.size else 0.0]
    return out


def norms_close(a: dict, b: dict, *, rtol: float = 1e-5) -> bool:
    """Same shard keys, every [l2, absmax] pair within rtol (relative to
    the larger magnitude, floored at 1.0 so zero buckets compare sanely)."""
    if a is None or b is None or not a or set(a) != set(b):
        return False
    for k in a:
        for x, y in zip(a[k], b[k]):
            if abs(x - y) > rtol * max(abs(x), abs(y), 1.0):
                return False
    return True


def run_elastic_worker(*, rounds: int, start_round: int = 0, workdir: str,
                       chaos: str = "", quantize: bool = True,
                       momentum: float = 0.0, seed: int = 0,
                       arch: str = "starcoder2-3b",
                       heartbeat_timeout: float = 30.0) -> dict:
    """One worker of one elastic GENERATION: W = the global device count
    (one dp lane per device, mesh Wx1), engine rounds under `--sync
    partial` with a manifest checkpoint at every round boundary.

    start_round > 0 resumes from the workdir's manifest via the engine's
    `restore_elastic` — written under ANY previous worker count: a shrunk
    generation drops the dead lane, a regrown one clones the consensus
    into the rejoined lane (core/engine.py).  start_round == rounds runs
    zero rounds — the restore-and-hash probe the checkpoint matrix test
    uses to prove manifest restores under different process counts.

    chaos="kill:worker=k,round=r": worker k os._exit()s at the START of
    global round r, before announcing its heartbeat — the survivors'
    await_peers times out and each returns a membership verdict (the CLI
    exits rc 3) naming the missing pids and the resume point.  A
    single-process run of the same mesh is the bitwise reference for any
    multi-process generation (quantized sync: integer-code domain)."""
    import jax
    from repro.launch.mesh import make_mesh
    import numpy as np

    from repro.configs import registry as R
    from repro.configs.base import RunConfig
    from repro.core.engine import RoundEngine
    from repro.optim.lr import make_lr_fn

    workers = len(jax.devices())
    jmesh = make_mesh((workers, 1), ("data", "model"))
    cfg = R.get_smoke_config(arch)
    run_cfg = RunConfig(schedule="constant", optimizer="adamw",
                        total_steps=2 * max(rounds, 1), peak_lr=3e-3,
                        warmup_steps=1, h_base=2, remat=False,
                        weight_decay=0.01, sync_quantize=quantize,
                        outer_momentum=momentum, sharding="dp")
    eng = RoundEngine(cfg, run_cfg, workers=workers, b_loc=2, seq=16,
                      seed=seed, data="device", layout="flat_sharded",
                      sync="partial", mesh=jmesh, policy="dp")
    lr_fn = make_lr_fn(run_cfg)
    state = eng.init_state()
    ckpt = os.path.join(workdir, "ckpt")
    if start_round > 0:
        state, step = eng.restore_elastic(ckpt, state)
        if step != 2 * start_round:
            raise RuntimeError(
                f"manifest at {ckpt} resumes at step {step}, this "
                f"generation starts at round {start_round} (step "
                f"{2 * start_round})")
    pid, nproc = jax.process_index(), jax.process_count()
    kind, kv = _parse_chaos(chaos)
    kill = ((kv.get("worker", -1), kv.get("round", -1))
            if kind == "kill" else None)
    # heartbeat dir is per-generation: stale announcements from a previous
    # epoch must not vouch for a pid that died in this one
    hb = Heartbeat(os.path.join(workdir, f"hb-e{start_round}x{nproc}"),
                   pid, nproc, timeout=heartbeat_timeout)
    barrier = _device_barrier if nproc > 1 else None
    losses = []
    for r in range(start_round, rounds):
        if kill == (pid, r):
            os._exit(7)       # the chaos monkey: no goodbye, no heartbeat
        hb.announce(r)
        missing = hb.await_peers(r)
        if missing:
            return {"mode": "elastic", "status": "membership-change",
                    "ok": True, "missing": missing, "resume_round": r,
                    "resume_step": 2 * r, "checkpoint": ckpt,
                    "rounds_done": r - start_round, **runtime_info()}
        state, m = eng.run_round(state, 2 * r, 2, lr_fn)
        losses.append(float(m["loss"]))
        eng.save_sharded(ckpt, state, step=2 * (r + 1), barrier=barrier)
        if nproc == 1:
            # the monolithic twin the manifest is proven shard-for-shard
            # bitwise against (tests/test_manifest_ckpt.py)
            eng.save(os.path.join(workdir, "ckpt-mono"), state,
                     step=2 * (r + 1))
    return {"mode": "elastic", "status": "complete",
            "ok": bool(np.all(np.isfinite(losses))) if losses else True,
            "losses": losses, "shard_hashes": _elastic_hashes(state),
            "shard_norms": _elastic_norms(state),
            "workers": workers, "rounds": rounds,
            "start_round": start_round, "checkpoint": ckpt,
            **runtime_info()}


# --------------------------------------------------------------------------
# Spawning
# --------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _port_bindable(port: int) -> bool:
    try:
        with socket.socket() as s:
            s.bind(("localhost", port))
        return True
    except OSError:
        return False


def _choose_coordinator_port(*, attempts: int = 5, backoff: float = 0.05,
                             candidates=None) -> int:
    """A coordinator port that is still bindable, retrying with backoff:
    the free-port probe inherently races with the eventual bind (another
    server can take the port in between), so losing one probe must cost a
    re-probe, not the whole spawn.  `candidates` injects the first picks —
    the port-collision test pre-binds one and watches the retry walk past
    it."""
    for i in range(attempts):
        port = (candidates[i] if candidates and i < len(candidates)
                else _free_port())
        if _port_bindable(port):
            return port
        time.sleep(backoff * (2 ** i))
    raise OSError(f"no bindable coordinator port after {attempts} attempts")


def _pin_device_count(flags: str, n: int) -> str:
    """Rewrite an XLA_FLAGS string so it pins exactly `n` simulated host
    devices (dropping any prior pin) — used identically for spawned workers
    and single-process runs so their meshes always agree."""
    base = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
    return (base + f" --xla_force_host_platform_device_count={n}").strip()


def spawn_workers(num_processes: int, *, total_devices: int = 8,
                  extra: tuple[str, ...] = (), timeout: int = 900,
                  port_candidates=None):
    """Launch N `python -m repro.launch.multihost` worker processes on this
    machine (localhost coordinator, `total_devices/N` simulated CPU devices
    each) and wait.  Returns [(returncode, stdout, stderr)] per process.
    The coordinator port is chosen with collision retry
    (`_choose_coordinator_port`) and each worker's `initialize()` retries
    with backoff, so neither a probe race nor a slow coordinator fails the
    spawn outright."""
    if total_devices % num_processes != 0:
        raise TopologyError(
            f"{total_devices} simulated devices not divisible over "
            f"{num_processes} processes")
    # a 1-process spawn needs no coordinator: it runs as a plain
    # single-process job (initialize() no-ops).  Wiring jax.distributed +
    # gloo around a single process that owns several devices deadlocks the
    # first eager cross-device gather (e.g. restore_elastic's lane remap
    # on a mesh-sharded state) — and the single-process BITWISE REFERENCE
    # runs are exactly that shape.
    port = (_choose_coordinator_port(candidates=port_candidates)
            if num_processes > 1 else None)
    procs = []
    for pid in range(num_processes):
        env = dict(os.environ)
        if port is not None:
            env["REPRO_COORDINATOR"] = f"localhost:{port}"
            env["REPRO_NUM_PROCESSES"] = str(num_processes)
            env["REPRO_PROCESS_ID"] = str(pid)
        else:
            env.pop("REPRO_COORDINATOR", None)
            env.pop("REPRO_NUM_PROCESSES", None)
            env.pop("REPRO_PROCESS_ID", None)
        env["REPRO_SPAWNED"] = "1"   # the spawner's XLA_FLAGS pin rules
        env["XLA_FLAGS"] = _pin_device_count(
            env.get("XLA_FLAGS", ""), total_devices // num_processes)
        env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "repro.launch.multihost", *extra],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    out = []
    for p in procs:
        try:
            so, se = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            so, se = p.communicate()
            se = (se or "") + "\n[spawn_workers] TIMEOUT"
        out.append((p.returncode, so, se))
    return out


def _epoch_results(results):
    """Parse one generation's per-process (rc, stdout, stderr): the last
    JSON line of each stdout, plus merged shard hashes/norms and rcs."""
    parsed, hashes, norms = [], {}, {}
    for rc, so, _ in results:
        rec = None
        for line in reversed((so or "").strip().splitlines()):
            try:
                rec = json.loads(line)
                break
            except (json.JSONDecodeError, ValueError):
                continue
        parsed.append(rec)
        if rec:
            hashes.update(rec.get("shard_hashes") or {})
            norms.update(rec.get("shard_norms") or {})
    return parsed, hashes, norms, [rc for rc, _, _ in results]


def run_elastic(num_workers: int, *, rounds: int = 3, chaos: str,
                seed: int = 0, arch: str = "starcoder2-3b",
                quantize: bool = True, momentum: float = 0.0,
                workdir: str | None = None,
                heartbeat_timeout: float = 30.0, timeout: int = 900,
                extra_rounds: int = 2) -> dict:
    """The fault-injection controller: drives worker GENERATIONS (each one
    engine MembershipEpoch — `jax.distributed` cannot resize in place)
    through a kill-and-recover story, proving each multi-process
    generation against a single-process run of the same mesh: the
    reduced-mesh CONSENSUS (params + anchor) bitwise in the quantized
    partial sync's integer-code domain, and the regrown rejoin
    generation within a tight norms/losses tolerance (lane-local f32
    math may drift by ulps across process layouts).

    --chaos kill:worker=k,round=r
      gen 0 (W workers):   rounds 0..r-1 complete; worker k dies at the
                           start of round r; survivors' heartbeat timeout
                           fires and they exit rc 3 with the verdict
      gen 1 (W-1 workers): resumes round r from the last round-boundary
                           manifest, completes the run on the reduced
                           mesh; consensus proven bitwise vs a 1-process
                           (W-1)-lane reference resuming the same
                           manifest, Adam moments within the norms
                           tolerance
    --chaos preempt-restore[:worker=k,round=r]
      ...then gen 2 (W workers again) rejoins the lost lane from gen 1's
      final manifest — a W-lane restore of a (W-1)-lane checkpoint under a
      different process count; the rejoined lane re-anchors to consensus —
      and runs `extra_rounds` more, proven within the tolerance bound
      (per-shard l2/absmax norms + per-round losses) vs a 1-process
      W-lane reference; the restore itself is bitwise (manifest matrix).

    Returns the recovery telemetry (the CI chaos job's JSON artifact):
    per-generation rcs/losses, the detection verdict, and the
    bitwise/tolerance verdicts."""
    kind, kv = _parse_chaos(chaos)
    if kind not in ("kill", "preempt-restore"):
        raise ValueError(f"unknown chaos spec {chaos!r}")
    k = kv.get("worker", num_workers // 2)
    r = kv.get("round", 1)
    if not (0 <= k < num_workers and 0 < r < rounds):
        raise ValueError(f"chaos worker={k}, round={r} out of range for "
                         f"{num_workers} workers x {rounds} rounds")
    workdir = workdir or tempfile.mkdtemp(prefix="repro-elastic-")
    os.makedirs(workdir, exist_ok=True)

    def fork(name: str) -> str:
        """A reference generation resumes the SAME manifest the live one
        does — but the live one then advances the rolling checkpoint, so
        the reference runs in a forked copy of the workdir."""
        import shutil
        dst = os.path.join(workdir, name)
        os.makedirs(dst, exist_ok=True)
        if os.path.isdir(os.path.join(workdir, "ckpt")):
            shutil.copytree(os.path.join(workdir, "ckpt"),
                            os.path.join(dst, "ckpt"), dirs_exist_ok=True)
        return dst

    def gen(lanes: int, total_rounds: int, start: int, *, procs=None,
            chaos_arg: str = "", wd: str | None = None):
        ex = ["--mode", "elastic", "--rounds", str(total_rounds),
              "--start-round", str(start), "--workdir", wd or workdir,
              "--momentum", str(momentum), "--seed", str(seed),
              "--arch", arch,
              "--heartbeat-timeout", str(heartbeat_timeout)]
        if quantize:
            ex.append("--quantize")
        if chaos_arg:
            ex += ["--chaos", chaos_arg]
        return _epoch_results(spawn_workers(
            procs or lanes, total_devices=lanes, extra=tuple(ex),
            timeout=timeout))

    out = {"mode": "elastic-controller", "chaos": chaos, "workers":
           num_workers, "rounds": rounds, "kill": {"worker": k, "round": r},
           "workdir": workdir, "generations": []}

    # generation 0: full worker set, chaos kill mid-run
    p0, _, _, rc0 = gen(num_workers, rounds, 0,
                        chaos_arg=f"kill:worker={k},round={r}")
    verdicts = [x for x in p0 if x and x.get("status") == "membership-change"]
    detect_ok = (
        rc0[k] == 7
        and all(rc == 3 for i, rc in enumerate(rc0) if i != k)
        and len(verdicts) == num_workers - 1
        and all(v["missing"] == [k] and v["resume_round"] == r
                for v in verdicts))
    out["generations"].append({"lanes": num_workers, "rcs": rc0,
                               "verdicts": verdicts, "detect_ok": detect_ok})
    if not detect_ok:
        out["ok"] = False
        return out

    # generation 1: survivors complete the run over the reduced mesh,
    # bitwise vs a single-process reference resuming the same manifest
    lanes1 = num_workers - 1
    ref1 = fork("ref1")
    p1, h1, n1, rc1 = gen(lanes1, rounds, r)
    pr, hr, nr, rcr = gen(lanes1, rounds, r, procs=1, wd=ref1)
    # the contractual BITWISE claim is the partial-mean consensus (params +
    # anchor: integer-code domain, exact under any process split); the
    # lane-local Adam moments are f32 trajectories compared within the
    # norms tolerance like gen 2 — XLA may fuse them differently per
    # process layout
    cons = lambda h: {k: v for k, v in h.items()
                      if not k.startswith("opt.")}
    recover_ok = (all(rc == 0 for rc in rc1 + rcr) and bool(h1)
                  and cons(h1) == cons(hr) and norms_close(n1, nr))
    out["generations"].append({
        "lanes": lanes1, "rcs": rc1, "reference_rcs": rcr,
        "rounds_redone": rounds - r,
        "losses": next((x.get("losses") for x in p1 if x), None),
        "reference_losses": next((x.get("losses") for x in pr if x), None),
        "bitwise_vs_single_process": cons(h1) == cons(hr),
        "moments_tolerance_ok": norms_close(n1, nr),
        "shards_compared": len(h1)})
    ok = detect_ok and recover_ok

    if kind == "preempt-restore" and ok:
        # generation 2: the lost lane rejoins from gen 1's final manifest.
        # The verdict here is the TOLERANCE bound, not bitwise: the manifest
        # RESTORE is proven bitwise under any process count (zero-round
        # probes; tests/test_manifest_ckpt.py), but a REGROWN worker set
        # compiles a different per-process XLA program whose lane-local f32
        # math can drift by ulps across process layouts — the sync stays
        # integer-exact, so live-vs-reference shard norms agree to ~1e-5
        # while a real restore/rejoin bug (wrong lane, zeroed moments)
        # lands orders of magnitude outside it.  Bitwise is still reported.
        total2 = rounds + extra_rounds
        ref2 = fork("ref2")
        p2, h2, n2, rc2 = gen(num_workers, total2, rounds)
        pr2, hr2, nr2, rcr2 = gen(num_workers, total2, rounds,
                                  procs=1, wd=ref2)
        l2 = next((x.get("losses") for x in p2 if x), None)
        lr2 = next((x.get("losses") for x in pr2 if x), None)
        losses_ok = (l2 is not None and lr2 is not None and len(l2) == len(lr2)
                     and all(abs(a - b) <= 1e-4 * max(abs(a), abs(b), 1.0)
                             for a, b in zip(l2, lr2)))
        rejoin_ok = (all(rc == 0 for rc in rc2 + rcr2)
                     and norms_close(n2, nr2) and losses_ok)
        out["generations"].append({
            "lanes": num_workers, "rcs": rc2, "reference_rcs": rcr2,
            "rejoined_from": "manifest", "extra_rounds": extra_rounds,
            "losses": l2, "reference_losses": lr2,
            "tolerance_vs_single_process": rejoin_ok,
            "bitwise_vs_single_process": h2 == hr2,
            "shards_compared": len(n2)})
        ok = ok and rejoin_ok

    out["ok"] = ok
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spawn", type=int, default=0,
                    help="launch N worker processes on this machine and "
                         "aggregate their JSON (0: run as a worker / "
                         "single process)")
    ap.add_argument("--total-devices", type=int, default=8,
                    help="global device count (split across --spawn "
                         "workers; pinned locally when single-process)")
    ap.add_argument("--mode", default="sync",
                    choices=["sync", "engine", "probe", "elastic"])
    ap.add_argument("--chaos", default="",
                    help="fault injection: 'kill:worker=K,round=R' or "
                         "'preempt-restore[:worker=K,round=R]'.  With "
                         "--spawn this runs the elastic controller across "
                         "worker generations (module docstring §3); for a "
                         "worker it names its own death sentence")
    ap.add_argument("--membership", default="",
                    help="sync mode: comma mask ('1,1,0,1') switching both "
                         "paths to the partial sync — masked lanes are "
                         "excluded from the mean, which divides by |P|; "
                         "quantized runs also assert the consensus bitwise "
                         "vs a |P|-worker run (integer-code domain)")
    ap.add_argument("--workdir", default="",
                    help="elastic mode: checkpoint/heartbeat directory "
                         "shared by the worker generations (controller "
                         "default: a fresh temp dir)")
    ap.add_argument("--start-round", type=int, default=0,
                    help="elastic mode: first round of this generation "
                         "(resumes the workdir manifest when > 0)")
    ap.add_argument("--heartbeat-timeout", type=float, default=30.0,
                    help="elastic mode: seconds before a silent peer is "
                         "declared dead at a round boundary")
    ap.add_argument("--out", default="",
                    help="also write the result JSON here (the CI chaos "
                         "job uploads the controller's recovery telemetry)")
    ap.add_argument("--mesh", default="2x2x2",
                    help="data x model or pod x data x model; the product "
                         "must equal --total-devices")
    ap.add_argument("--policy", default="fsdp", choices=["dp", "fsdp"])
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--wire", default="auto", choices=["auto", "ring-int8"],
                    help="quantized payload wire mode: 'auto' = exact "
                         "int16/int32 code-sums (bitwise asserts); "
                         "'ring-int8' = re-quantizing int8 ppermute ring "
                         "(tolerance asserts; implies --quantize)")
    ap.add_argument("--momentum", type=float, default=0.0)
    ap.add_argument("--overlap", action="store_true",
                    help="sync mode: split begin/apply across round "
                         "boundaries (the engine's --sync overlap seam)")
    ap.add_argument("--sync", default="blocking",
                    choices=["blocking", "overlap"],
                    help="engine mode: run the RoundEngine rounds with the "
                         "pending reduce threaded across program boundaries "
                         "(--sync overlap); a blocking engine runs alongside "
                         "as the in-process bitwise reference at depth 0")
    ap.add_argument("--overlap-depth", type=int, default=0,
                    help="engine mode: local steps run on stale params "
                         "before the deferred gather applies")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arch", default="starcoder2-3b")
    args = ap.parse_args()
    if args.wire == "ring-int8":
        args.quantize = True

    def emit(out: dict) -> None:
        if args.out:
            with open(args.out, "w") as f:
                json.dump(out, f, indent=2)
        print(json.dumps(out))

    if args.spawn and args.chaos:
        # the elastic controller: no jax in THIS process — it only spawns
        # worker generations and judges their verdicts/hashes
        out = run_elastic(args.spawn, rounds=args.rounds, chaos=args.chaos,
                          seed=args.seed, arch=args.arch,
                          quantize=args.quantize, momentum=args.momentum,
                          workdir=args.workdir or None,
                          heartbeat_timeout=args.heartbeat_timeout)
        emit(out)
        sys.exit(0 if out["ok"] else 1)

    if args.spawn:
        extra = ["--mode", args.mode, "--mesh", args.mesh,
                 "--policy", args.policy, "--momentum", str(args.momentum),
                 "--rounds", str(args.rounds), "--seed", str(args.seed),
                 "--arch", args.arch, "--sync", args.sync,
                 "--overlap-depth", str(args.overlap_depth),
                 "--wire", args.wire,
                 "--start-round", str(args.start_round),
                 "--heartbeat-timeout", str(args.heartbeat_timeout)]
        if args.quantize:
            extra.append("--quantize")
        if args.overlap:
            extra.append("--overlap")
        if args.membership:
            extra += ["--membership", args.membership]
        if args.workdir:
            extra += ["--workdir", args.workdir]
        results = spawn_workers(args.spawn, total_devices=args.total_devices,
                                extra=tuple(extra))
        ok = all(rc == 0 for rc, _, _ in results)
        for i, (rc, so, se) in enumerate(results):
            print(f"--- process {i} (rc={rc}) ---")
            print(so.strip())
            if rc != 0:
                print(se[-2000:], file=sys.stderr)
        sys.exit(0 if ok else 1)

    # worker (REPRO_COORDINATOR set by the spawner) or single-process run;
    # single-process: pin the simulated device count before jax wakes up —
    # unless a spawner already pinned it (REPRO_SPAWNED: a coordinator-less
    # 1-process spawn pins total_devices in XLA_FLAGS; re-pinning here
    # would override it with this CLI's --total-devices default)
    if ("REPRO_COORDINATOR" not in os.environ
            and "REPRO_SPAWNED" not in os.environ
            and "jax" not in sys.modules):
        os.environ["XLA_FLAGS"] = _pin_device_count(
            os.environ.get("XLA_FLAGS", ""), args.total_devices)
    initialize()
    if args.mode == "probe":
        out = probe()
    elif args.mode == "elastic":
        out = run_elastic_worker(
            rounds=args.rounds, start_round=args.start_round,
            workdir=args.workdir or tempfile.mkdtemp(prefix="repro-el-"),
            chaos=args.chaos, quantize=args.quantize,
            momentum=args.momentum, seed=args.seed, arch=args.arch,
            heartbeat_timeout=args.heartbeat_timeout)
        emit(out)
        if out.get("status") == "membership-change":
            # rc 3 = the membership verdict; os._exit skips jax.distributed
            # teardown, which can hang once a peer is dead
            sys.stdout.flush()
            os._exit(3)
        sys.exit(0 if out["ok"] else 1)
    elif args.mode == "engine":
        out = run_engine(mesh=args.mesh, policy=args.policy,
                         quantize=args.quantize, momentum=args.momentum,
                         rounds=args.rounds, seed=args.seed, arch=args.arch,
                         sync=args.sync, overlap_depth=args.overlap_depth,
                         wire=args.wire)
    else:
        out = run_sync(mesh=args.mesh, policy=args.policy,
                       quantize=args.quantize, momentum=args.momentum,
                       overlap=args.overlap, rounds=args.rounds,
                       seed=args.seed, wire=args.wire,
                       membership=args.membership)
    emit(out)
    sys.exit(0 if out["ok"] else 1)


if __name__ == "__main__":
    main()
