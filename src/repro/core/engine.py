"""RoundEngine: one runtime that owns compilation, state, and data for a
training run — shared by the local-gradient path (paper Alg. 2, any
H-schedule) and the data-parallel baseline (Alg. 1 == the same engine with
H=1 every round).

Why it exists: QSR grows H as (alpha/eta)^2 while the lr decays (PAPER.md
eq. 2), so a real run visits many distinct H values.  Jitting a fresh
`train_round` per raw H makes compile time scale with the *schedule*; this
engine makes it scale with the *hardware* (log of the largest round).

## Bucketing / mask contract

* Every requested H is bucketed up to the next power of two
  `Hp = bucket_pow2(H)`; one round program is compiled per bucket, so a full
  QSR schedule compiles at most `ceil(log2(H_max)) + 1` programs instead of
  one per distinct H.
* A bucketed program scans Hp steps with a per-step validity mask
  (`step i valid iff i < h`).  Each scan step is a `lax.cond` on the mask:
  a masked step skips the local step entirely (state — including the
  optimizer step counter — passes through unchanged, no FLOPs spent),
  contributes 0 to the loss / grad-norm sums, and the round mean divides by
  h, not Hp.  Loss, lr, and sync semantics are therefore exact for any
  h <= Hp, and because the valid-step computation lives in its own cond
  branch it stays bitwise-identical to an unpadded scan over the same
  batches (verified by tests/test_engine.py).
* State buffers are donated to the round program (`donate_argnums=0`) when
  the backend supports it, so params/optimizer memory is reused across
  rounds instead of doubled.

## Data modes

* `data="device"`: batches are synthesized *inside* the jitted round from
  `jax.random.fold_in(seed, global_step)` (data/synthetic.py
  `device_batch_fn`) — no host-side `[H, W, B, S]` stack, no host->device
  transfer per round.
* `data="host"`: the legacy numpy TokenStream path, kept for
  reproducibility tests and real-data loaders.

## Telemetry

Each round returns in-graph metrics (computed in the same program, no extra
device round-trips): the loss and worker-mean global grad norm, each
averaged over the round's valid steps, and the pre-sync worker divergence
`mean_i ||x_i - x_bar||_2` — the quantity the paper's SDE analysis ties to
the generalization benefit of large H.

The round's parts run under `jax.named_scope`s — `grad` (forward and
backward of the local step), `optimizer`, `telemetry` (the grad-norm sums
and `_metrics`) and `sync` — so each op of the compiled round names, in its
HLO `op_name`, the part it belongs to; `run_round` puts host spans
(`repro.engine.*`) around its own work.  Neither changes the program.

## Param layouts

`layout="flat"` carries the run state as FlatParamSpace dtype buckets
(core/flat.py) end-to-end: donation still applies (the state is just a
smaller pytree of bigger buffers), telemetry reads norms off the flat
buffers in one reduction per bucket, sync is one all-reduce per bucket, and
the optimizer is one fused kernel per bucket.  Valid-step params match the
tree layout bitwise (tests/test_flat.py); only the reduction *order* inside
scalar metrics differs (per-bucket instead of per-leaf partial sums).

`layout="flat_sharded"` pads each bucket so it splits into per-device
contiguous chunks (core/flat.py ShardedFlatSpace).  Under a sharded mesh
the sync decomposes into one reduce_scatter + one all_gather per bucket
(core/sync.py); in the host loop the same state layout runs the flat path
on the padded buffers, bitwise-equal to tree/flat (tests/test_sharded.py).

## Sync modes

`sync="blocking"` (default): every round ends with the full sync — reduce,
outer update, and broadcast in the round program, exactly Alg. 1/2.

`sync="partial"`: the boundary sync averages over the workers that
*arrived* — each round takes a membership mask `[W]` as a traced argument
(no recompile when participation changes) and the mean divides by |P|, the
participant count, instead of W (core/sync.py make_sync_partial).  A
masked lane still runs the boundary collective (it is alive, just late or
untrusted), so it re-anchors to the participants' consensus at the same
boundary — its round's local progress is excluded from the mean and
discarded, which IS the rejoin rule: the next round it participates it
starts from consensus.  A lane that is *gone* (dead process) instead
leaves through a resize — `membership_epoch(keep_lanes=...)`, or for mesh
worlds the checkpoint + respawn path (launch/multihost.py run_elastic).
Membership may only change at a round boundary, through
`membership_epoch()` — the MembershipEpoch record is the audit trail.  The same call resizes the worker axis itself (lanes leave or
join between rounds): the state is re-padded through the tree layout, the
`ShardedFlatSpace` rebuilt for the new W, and the compile cache — keyed by
(Hp, W) — keeps the old-W programs parked so a reverted membership change
recompiles nothing.

`sync="overlap"`: the round program ends with only the *reduce* half
(core/sync.py make_sync_begin) and hands the engine a pending mean; the
*gather/apply* half runs inside the NEXT round's program, after its first
`overlap_depth` local steps — so the gather leg rides the wire while the
next round's compute is already running.  Depth 0 applies the pending sync
before the next round's first step: every local step then sees bitwise the
params it would under blocking sync (the exactness mode; `flush()` aligns
the final state).  Depth d > 0 lets workers run d steps on their own stale
params and applies the consensus as a correction
`x_i <- x_i + (consensus - x_i_at_boundary)` — local progress is kept, a
beyond-paper staleness/overlap tradeoff recorded in
benchmarks/table4_walltime.py rather than asserted.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as ckpt_io
from repro.core import flat
from repro.core import local_update as LU
from repro.core import schedules
from repro.core.sync import (make_sync, make_sync_apply, make_sync_begin,
                             make_sync_partial)
from repro.data.synthetic import (TokenStream, device_batch_fn,
                                  effective_batch_view, make_train_batch)
from repro.errors import ConfigError
from repro.models import api, common as cm, param as pm

Pytree = Any


class PendingSyncError(RuntimeError):
    """An overlap-mode sync is still in flight where a synced state is
    required.  A real exception, not a bare `assert`: checkpoint/readout
    paths run under `python -O`, which strips asserts — a stripped guard
    would silently hand out (or persist) pre-consensus params."""


class MembershipError(RuntimeError):
    """An illegal worker-set change: membership may only move at a round
    boundary (never with a sync in flight), masks must keep at least one
    participant, and mesh-backed engines resize their worker axis through
    checkpoint + respawn (launch/multihost.py run_elastic), never in-place
    — `jax.distributed` cannot shrink a live process group.  Survives
    `python -O` for the same reason PendingSyncError does."""


@dataclasses.dataclass(frozen=True)
class MembershipEpoch:
    """One round-boundary change of the worker set — the audit record
    `membership_epoch()` appends to `engine.epochs`.

    index:      epoch ordinal (0 = the run's initial membership)
    workers:    worker-axis size W after the change
    membership: the participation mask in force, one float per lane
    resized:    True when the W axis itself changed (lanes joined/left),
                False for a pure participation-mask change
    parked:     compile-cache keys left unreachable by a resize — still
                cached, so reverting to that W recompiles nothing
    """
    index: int
    workers: int
    membership: tuple[float, ...]
    resized: bool
    parked: tuple = ()


@dataclasses.dataclass(frozen=True)
class BatchEpoch:
    """One round-boundary change of the effective per-worker batch — the
    audit record `batch_epoch()` appends to `engine.batch_epochs` (the
    MembershipEpoch of the batch knob).  The effective batch is a *traced*
    lane count over the allocated [W, b_loc, ...] batch buffers
    (data/synthetic.py effective_batch_view), so a BatchEpoch never
    recompiles anything.

    index:       epoch ordinal
    lanes:       effective per-worker batch after the change (divides b_loc)
    b_loc:       the allocated per-worker batch (compiled shape, unchanged)
    round_index: rounds executed when the change landed (the boundary)
    """
    index: int
    lanes: int
    b_loc: int
    round_index: int


# --------------------------------------------------------------------------
# Bucketing
# --------------------------------------------------------------------------

def bucket_pow2(h: int) -> int:
    """Smallest power of two >= h (the compile-cache key)."""
    return 1 if h <= 1 else 1 << (h - 1).bit_length()


def schedule_buckets(run_cfg, lr_fn) -> list[int]:
    """Distinct power-of-two buckets a full schedule visits, ascending."""
    return sorted({bucket_pow2(h) for _, h in schedules.rounds(run_cfg, lr_fn)})


def program_bound(h_max: int) -> int:
    """Compile-cache bound for a run whose largest round is h_max:
    ceil(log2 Hmax)+1 possible power-of-two buckets."""
    return int(math.ceil(math.log2(h_max))) + 1 if h_max > 1 else 1


def max_programs(run_cfg, lr_fn) -> int:
    """Upper bound on compiled round programs for a full schedule."""
    return program_bound(max(h for _, h in schedules.rounds(run_cfg, lr_fn)))


def enumerate_program_keys(run_cfg, lr_fn, *, sync: str = "blocking",
                           mode: str = "bucketed", overlap_depth: int = 0,
                           workers: int = 1) -> list[tuple]:
    """Statically enumerate the compile-cache keys a full schedule visits,
    in first-visit order — the lowering hook behind the static audit's
    compile-cache-bound rule (repro.analysis.rules), with zero compiles.

    Mirrors `RoundEngine._program`'s key derivation exactly: overlap keys
    on (hp, apply_pending, depth, W) — the first round of a run has no
    pending sync, every later round does — everything else on (hp, W).
    For a bucketed run the count must stay within `program_bound(Hmax)`
    (+1 under overlap for the pending-free first-round program)."""
    keys: list[tuple] = []
    pending = False
    for _, h in schedules.rounds(run_cfg, lr_fn):
        hp = bucket_pow2(h) if mode == "bucketed" else h
        key = ((hp, pending, overlap_depth, workers) if sync == "overlap"
               else (hp, workers))
        if key not in keys:
            keys.append(key)
        if sync == "overlap":
            pending = True
    return keys


# --------------------------------------------------------------------------
# In-graph telemetry
# --------------------------------------------------------------------------

def worker_divergence(params: Pytree) -> jax.Array:
    """mean_i ||x_i - x_bar||_2 over the leading worker axis, all leaves."""
    sq = 0.0
    for x in jax.tree.leaves(params):
        xf = x.astype(jnp.float32)
        m = jnp.mean(xf, axis=0, keepdims=True)
        sq = sq + jnp.sum(jnp.square(xf - m), axis=tuple(range(1, xf.ndim)))
    return jnp.mean(jnp.sqrt(sq))


def _metrics(state, losses, gns, denom):
    with jax.named_scope("telemetry"):
        div = worker_divergence(state["params"])
        return {"loss": jnp.sum(losses) / denom,
                "grad_norm": jnp.sum(gns) / denom,
                "divergence": div}


# --------------------------------------------------------------------------
# Round-program builders (module-level so launch/shapes.py can lower them
# without an engine instance)
# --------------------------------------------------------------------------

def _remap_worker_lanes(tree_state: Pytree, lanes: list[int]) -> Pytree:
    """Tree-layout state with its worker axis re-padded to `lanes` (source
    lane per new slot; repeating a lane clones it — params AND moments, so
    a joined lane starts as a consensus replica).  Anchors, outer momentum,
    and the shared step counter carry no worker axis and pass through."""
    take = lambda x: jnp.stack([x[i] for i in lanes])
    out = dict(tree_state)
    out["params"] = jax.tree.map(take, tree_state["params"])
    out["opt"] = {k: (jax.tree.map(take, v) if k in flat._STACKED else v)
                  for k, v in tree_state["opt"].items()}
    return out


def _masked_body(local_step):
    """Per-step masked executor shared by the bucketed/overlap rounds.

    lax.cond keeps the valid-step computation an isolated XLA
    subcomputation: valid steps stay bitwise-identical to the unpadded
    program (a jnp.where select would perturb fusion at ulp level) and
    masked steps skip their FLOPs instead of computing-and-discarding.
    get_batch is called *inside* the taken branch so device-mode synthesis
    is skipped on masked steps too (a closed-over batch value would be an
    unconditionally-computed cond operand)."""
    def body(st, get_batch, lr, valid):
        def do(st):
            st2, (loss, gn) = local_step(st, get_batch(), lr)
            return st2, loss, gn
        def skip(st):
            return st, jnp.float32(0.0), jnp.float32(0.0)
        st2, loss, gn = jax.lax.cond(valid, do, skip, st)
        return st2, (loss, gn)
    return body


def _lane_viewer(batch_arg: bool, lanes):
    """Per-step batch transform for the round builders: with `batch_arg`
    the effective batch is the traced `lanes` count (a pure gather view,
    applied inside the valid-step cond branch so masked steps skip it);
    without, the identity."""
    if not batch_arg:
        return lambda b: b
    return lambda b: effective_batch_view(b, lanes, axis=1)


def make_bucketed_round(cfg, run_cfg, synth: Callable | None = None,
                        spec=None, *, batch_arg: bool = False,
                        local_workers: bool = False):
    """Padded, masked communication round.

    Host data:   fn(state, batches [Hp, W, B, ...], lrs [Hp], mask [Hp])
    Device data: fn(state, t0 scalar, lrs [Hp], mask [Hp])  (synth given)
    -> (state, {"loss", "grad_norm", "divergence"}).

    With `spec` (core.flat.FlatParamSpace) the state is flat dtype buckets
    end-to-end: params/opt {bucket: [W, N]}, the sync one collective per
    bucket, the telemetry one reduction per bucket.

    With `batch_arg` the signature gains a trailing traced int32 scalar
    `lanes` — the *effective* per-worker batch (adaptive-controller knob):
    each step trains on samples [0, lanes) tiled over the allocated b_loc
    slots (data/synthetic.py effective_batch_view — exact batch-`lanes`
    gradients when lanes divides b_loc, bitwise pass-through at
    lanes == b_loc), so the effective batch changes between rounds without
    recompiling.
    """
    local_step = LU.make_local_step(cfg, run_cfg, with_metrics=True,
                                    spec=spec, local_workers=local_workers)
    sync = make_sync(run_cfg, spec=spec)
    body = _masked_body(local_step)

    def finish(state, losses, gns, mask):
        denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
        m = _metrics(state, losses, gns, denom)
        return sync(state), m

    if synth is None:
        def round_fn(state, batches, lrs, mask, *lanes):
            view = _lane_viewer(batch_arg, lanes[0] if batch_arg else None)
            def step(st, xs):
                batch, lr, valid = xs
                return body(st, lambda: view(batch), lr, valid)
            state, (losses, gns) = jax.lax.scan(
                step, state, (batches, lrs, mask), unroll=cm.scan_unroll())
            return finish(state, losses, gns, mask)
    else:
        def round_fn(state, t0, lrs, mask, *lanes):
            view = _lane_viewer(batch_arg, lanes[0] if batch_arg else None)
            hp = lrs.shape[0]
            def step(st, xs):
                i, lr, valid = xs
                return body(st, lambda: view(synth(t0 + i)), lr, valid)
            state, (losses, gns) = jax.lax.scan(
                step, state, (jnp.arange(hp), lrs, mask),
                unroll=cm.scan_unroll())
            return finish(state, losses, gns, mask)

    return round_fn


def make_partial_round(cfg, run_cfg, synth: Callable | None = None,
                       spec=None, *, batch_arg: bool = False,
                       local_workers: bool = False):
    """Bucketed round whose boundary sync averages over ARRIVED workers.

    Host data:   fn(state, membership [W], batches [Hp,...], lrs, mask)
    Device data: fn(state, membership [W], t0 scalar, lrs, mask)
    -> (state, metrics).

    `membership` is a float mask over the worker axis, a *traced* argument:
    the participant set changes round to round without recompiling.  All W
    lanes still run their local steps (a straggler's compute is its own
    loss); only the boundary mean is restricted — Σ masked deltas / |P|,
    exact in the integer-code domain under quantized sync (core/sync.py
    §Partial participation).  The apply then broadcasts the participants'
    consensus to every lane, masked ones included: an excluded round's
    local progress is discarded and the lane re-anchors, so it rejoins
    from consensus.  Lanes whose PROCESS is gone leave through
    membership_epoch resize / run_elastic instead — they cannot run a
    collective at all.
    """
    local_step = LU.make_local_step(cfg, run_cfg, with_metrics=True,
                                    spec=spec, local_workers=local_workers)
    sync = make_sync_partial(run_cfg, spec=spec)
    body = _masked_body(local_step)

    def finish(state, membership, losses, gns, mask):
        denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
        m = _metrics(state, losses, gns, denom)
        return sync(state, membership), m

    if synth is None:
        def round_fn(state, membership, batches, lrs, mask, *lanes):
            view = _lane_viewer(batch_arg, lanes[0] if batch_arg else None)
            def step(st, xs):
                batch, lr, valid = xs
                return body(st, lambda: view(batch), lr, valid)
            state, (losses, gns) = jax.lax.scan(
                step, state, (batches, lrs, mask), unroll=cm.scan_unroll())
            return finish(state, membership, losses, gns, mask)
    else:
        def round_fn(state, membership, t0, lrs, mask, *lanes):
            view = _lane_viewer(batch_arg, lanes[0] if batch_arg else None)
            hp = lrs.shape[0]
            def step(st, xs):
                i, lr, valid = xs
                return body(st, lambda: view(synth(t0 + i)), lr, valid)
            state, (losses, gns) = jax.lax.scan(
                step, state, (jnp.arange(hp), lrs, mask),
                unroll=cm.scan_unroll())
            return finish(state, membership, losses, gns, mask)

    return round_fn


def make_exact_round(cfg, run_cfg, synth: Callable | None = None, spec=None,
                     *, local_workers: bool = False):
    """Legacy exact-H round (one compile per distinct H) + engine telemetry.

    Same state arithmetic as `local_update.make_train_round`; kept as the
    escape hatch (`--engine legacy`) and the reference the bucketed path is
    tested bitwise against.
    """
    local_step = LU.make_local_step(cfg, run_cfg, with_metrics=True,
                                    spec=spec, local_workers=local_workers)
    sync = make_sync(run_cfg, spec=spec)

    def finish_exact(state, losses, gns):
        m = _metrics(state, losses, gns, jnp.float32(losses.shape[0]))
        return sync(state), m

    if synth is None:
        def round_fn(state, batches, lrs):
            def step(st, xs):
                batch, lr = xs
                st, (loss, gn) = local_step(st, batch, lr)
                return st, (loss, gn)
            state, (losses, gns) = jax.lax.scan(step, state, (batches, lrs),
                                                unroll=cm.scan_unroll())
            return finish_exact(state, losses, gns)
    else:
        def round_fn(state, t0, lrs):
            h = lrs.shape[0]
            def step(st, xs):
                i, lr = xs
                st, (loss, gn) = local_step(st, synth(t0 + i), lr)
                return st, (loss, gn)
            state, (losses, gns) = jax.lax.scan(
                step, state, (jnp.arange(h), lrs), unroll=cm.scan_unroll())
            return finish_exact(state, losses, gns)

    return round_fn


def make_overlap_round(cfg, run_cfg, synth: Callable | None = None,
                       spec=None, *, depth: int = 0,
                       apply_pending: bool = True, batch_arg: bool = False,
                       local_workers: bool = False):
    """Bucketed round with the sync split across the round boundary.

    Host data:   fn(state, pending?, batches [Hp, ...], lrs [Hp], mask [Hp])
    Device data: fn(state, pending?, t0 scalar, lrs [Hp], mask [Hp])
    -> (state, new_pending, metrics).  `pending?` is present iff
    `apply_pending` (every round but the first).

    The program: run the first min(depth, Hp) local steps on the stale
    (pre-consensus) params, gather+apply the previous round's pending
    reduce (exact assignment at depth 0; correction form otherwise), run
    the remaining steps, and end with only the *reduce* half of this
    round's sync — new_pending, handed to the next program.
    """
    local_step = LU.make_local_step(cfg, run_cfg, with_metrics=True,
                                    spec=spec, local_workers=local_workers)
    begin = make_sync_begin(run_cfg, spec=spec)
    apply_ = make_sync_apply(run_cfg, spec=spec)
    body = _masked_body(local_step)

    def round_fn(state, *args):
        if batch_arg:
            *args, lanes = args
        view = _lane_viewer(batch_arg, lanes if batch_arg else None)

        if synth is None:
            def step(st, xs):
                batch, lr, valid = xs
                return body(st, lambda: view(batch), lr, valid)
        else:
            def step(st, xs):
                i, lr, valid = xs
                return body(st, lambda: view(synth(i)), lr, valid)

        def segment(state, xs):
            return jax.lax.scan(step, state, xs, unroll=cm.scan_unroll())

        if apply_pending:
            pending, *rest = args
        else:
            rest = args
        data, lrs, mask = rest
        hp = lrs.shape[0]
        xs = ((data, lrs, mask) if synth is None
              else (data + jnp.arange(hp), lrs, mask))
        d = min(depth, hp) if apply_pending else 0
        take = lambda a, b: jax.tree.map(lambda x: x[a:b], xs)
        losses, gns = [], []
        if apply_pending:
            if d > 0:
                entry = state["params"]
                state, (l1, g1) = segment(state, take(0, d))
                losses.append(l1)
                gns.append(g1)
                state = apply_(state, pending, entry)
            else:
                state = apply_(state, pending)
        state, (l2, g2) = segment(state, take(d, hp))
        losses.append(l2)
        gns.append(g2)
        cat = lambda ps: ps[0] if len(ps) == 1 else jnp.concatenate(ps)
        denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
        m = _metrics(state, cat(losses), cat(gns), denom)
        return state, begin(state), m

    return round_fn


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------

class RoundEngine:
    """Owns the compile cache, run state, data source, and H-trace of a run.

    mode:   "bucketed" (power-of-two compile cache, masked scan — default) |
            "legacy"   (one program per distinct H — the seed behavior)
    data:   "device" (in-graph fold_in batch synthesis — default) |
            "host"   (numpy TokenStream, batches staged per round)
    layout: "tree" (state mirrors the model pytree — default) |
            "flat" (state is a few dtype-bucketed [W, N] buffers, see
            core/flat.py: one sync all-reduce and one optimizer kernel per
            bucket instead of per leaf; bitwise-equal trajectories) |
            "flat_sharded" (flat buckets padded into `shards` contiguous
            per-device chunks — the FSDP-style layout whose sync lowers to
            reduce_scatter + all_gather under a mesh; bitwise-equal too)
    sync:   "blocking" (round ends fully synced — default) |
            "overlap" (reduce at the boundary, gather/apply deferred past
            the next round's first `overlap_depth` steps; bucketed mode
            only; depth 0 is bitwise the blocking trajectory — see the
            module docstring.  `flush()` applies the last in-flight sync.
            Composes with `mesh=`: the pending reduce is threaded through
            the jitted round programs, its worker-sharded payload living
            on the mesh's devices — across real `jax.distributed`
            processes — between rounds (launch/multihost.py --mode engine
            --sync overlap).  Observers read `synced_view()`; checkpoints
            use `save(flush_pending=True)` or `flush()` — `save` raises
            PendingSyncError rather than persist pre-consensus params.)
    shards: chunk count for layout="flat_sharded" (0 -> workers, or the
            full device count when a mesh is given).
    mesh:   optional jax Mesh (layout="flat_sharded" only): the spec then
            carries the mesh + worker/shard axes (from `policy`), the state
            is laid out onto it at init (global arrays — works across real
            processes, launch/multihost.py), and the sync executes its
            explicit reduce_scatter / all_gather collectives instead of the
            host flat path.  Bitwise-equal to the mesh-less engine for
            quantized sync (integer-code reduction, core/sync.py) and for
            any sync when the worker-axis product is 2.
    policy: sharding policy naming the mesh's worker axes ("dp" | "fsdp");
            only read when a mesh is given.
    batch_fn: host-data override — `fn(step) -> batch [W, B_loc, ...]`
            replacing the built-in TokenStream (e.g. a VisionStream source
            for the paper's ViT runs).  Implies data="host".

    The data-parallel baseline (Alg. 1) is this same engine driven with the
    "parallel" schedule: every round has H=1, so workers sync (average) after
    each step — for SGD this is step-for-step the global-batch baseline.
    """

    def __init__(self, cfg, run_cfg, *, workers: int, b_loc: int, seq: int,
                 seed: int = 0, mode: str = "bucketed", data: str = "device",
                 layout: str = "tree", sync: str = "blocking",
                 overlap_depth: int = 0, shards: int = 0,
                 mesh=None, policy: str = "dp",
                 donate: bool | None = None,
                 batch_fn: Callable | None = None,
                 adaptive_batch: bool = False):
        if mode not in ("bucketed", "legacy"):
            raise ConfigError(f"unknown engine mode {mode!r}")
        if data not in ("device", "host"):
            raise ConfigError(f"unknown data source {data!r}")
        if layout not in ("tree", "flat", "flat_sharded"):
            raise ConfigError(f"unknown param layout {layout!r}")
        if sync not in ("blocking", "overlap", "partial"):
            raise ConfigError(f"unknown sync mode {sync!r}")
        if overlap_depth < 0:
            raise ConfigError(f"overlap_depth must be >= 0, got {overlap_depth}")
        if mesh is not None and layout != "flat_sharded":
            raise ConfigError(
                "a mesh drives the explicit-collective sync: layout=flat_sharded")
        if mesh is not None:
            got = pm.worker_count(policy, mesh)
            if got != workers:
                raise ConfigError(
                    f"policy {policy!r} on this mesh has {got} workers, "
                    f"engine built with {workers}")
        self.mesh, self.policy = mesh, policy
        if sync != "blocking" and mode != "bucketed":
            raise ConfigError(
                "overlap/partial sync runs through the bucketed program")
        if batch_fn is not None and data != "host":
            raise ConfigError("batch_fn is a host-data source; pass data='host'")
        if cfg.family == "vision" and not (data == "host" and batch_fn):
            raise ConfigError(
                "vision configs need data='host' and an image batch_fn")
        if adaptive_batch and mode != "bucketed":
            raise ConfigError(
                "the traced effective-batch lane rides the bucketed programs")
        self.cfg, self.run_cfg = cfg, run_cfg
        self.workers, self.b_loc, self.seq, self.seed = workers, b_loc, seq, seed
        self.mode, self.data, self.layout = mode, data, layout
        self.sync_mode, self.overlap_depth = sync, overlap_depth
        self.shards = shards
        self._pending = None          # overlap mode: in-flight reduce
        self._flush_fn = None
        # elastic membership: participation mask over the worker axis (all
        # lanes arrive by default) + the epoch audit trail.  Only
        # membership_epoch() may change either — and only between rounds.
        self.membership = np.ones(workers, np.float32)
        self.epochs: list[MembershipEpoch] = []
        # adaptive effective batch: the compiled shape is always b_loc; the
        # traced lane count below selects the effective batch per round
        # (batch_epoch() is the only legal change point — a round boundary)
        self.adaptive_batch = adaptive_batch
        self.batch_lanes = b_loc
        self.batch_epochs: list[BatchEpoch] = []
        # donation is a no-op warning on CPU; auto-enable elsewhere
        self.donate = (jax.default_backend() != "cpu") if donate is None else donate
        self.stream = TokenStream(vocab=max(cfg.vocab, 2), seed=seed)
        self._synth = (device_batch_fn(cfg, self.stream, workers, b_loc, seq)
                       if data == "device" else None)
        self._host_batch = batch_fn or (
            lambda step: make_train_batch(self.cfg, self.stream, step,
                                          self.workers, self.b_loc, self.seq))
        self.spec = None                           # FlatParamSpace (layout="flat")
        self._programs: dict[int, Any] = {}
        self._unlaunched: dict[Any, tuple] = {}    # built, not yet called
        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0                       # first calls of programs
        self.compiled_at: list[tuple[int, tuple]] = []   # (t, key)
        self.h_trace: list[tuple[int, int]] = []   # (t_start, h) executed

    # -- state ------------------------------------------------------------

    def _ensure_spec(self, params_single: Pytree | None = None):
        """The FlatParamSpace is recorded once, from the first params seen
        (or the config's abstract params) — after that all flatten/unflatten
        layout ops reuse it."""
        if self.spec is None:
            if params_single is None:
                mod = api.get_module(self.cfg)
                params_single = pm.abstract_params(mod.param_defs(self.cfg),
                                                   jnp.float32)
            if self.layout == "flat_sharded" and self.mesh is not None:
                waxes = pm.worker_mesh_axes(self.policy, self.mesh)
                saxes = tuple(a for a in self.mesh.axis_names
                              if a not in waxes)
                sizes = pm.mesh_axis_sizes(self.mesh)
                shards = self.shards or math.prod(sizes.values())
                self.spec = flat.ShardedFlatSpace(
                    params_single, shards, mesh=self.mesh,
                    worker_axes=waxes, shard_axes=saxes)
            elif self.layout == "flat_sharded":
                self.spec = flat.ShardedFlatSpace(params_single,
                                                  self.shards or self.workers)
            else:
                self.spec = flat.FlatParamSpace(params_single)
        return self.spec

    def init_state(self, params_single: Pytree | None = None) -> Pytree:
        if params_single is None:
            mod = api.get_module(self.cfg)
            params_single = pm.init_params(mod.param_defs(self.cfg),
                                           jax.random.PRNGKey(self.seed),
                                           jnp.float32)
        # on a mesh, one worker's state: _to_global broadcasts it over the
        # worker rows shard by shard, so the W-stacked state is never built
        # whole on one device
        w = 1 if self.mesh is not None else self.workers
        state = LU.init_state(self.cfg, self.run_cfg, params_single, w)
        if self.layout != "tree":
            state = flat.to_flat_state(self._ensure_spec(params_single), state)
        if self.mesh is not None:
            state = self._to_global(state)
        return state

    def _to_global(self, state: Pytree) -> Pytree:
        """Lay the flat state out onto the engine's mesh as global arrays
        (flat.make_global: works single-process and across real
        `jax.distributed` processes alike).  Worker-stacked [W, N] leaves
        (two-entry specs) may come with a single row, which every worker
        then starts from."""
        leaves, td = jax.tree.flatten(state)
        out = []
        for x, sh in zip(leaves, self._state_shardings(td)):
            shape = np.shape(x)
            if len(sh.spec) == 2:
                shape = (self.workers,) + shape[1:]
            out.append(flat.make_global(x, self.mesh, sh.spec, shape=shape))
        return jax.tree.unflatten(td, out)

    def _state_shardings(self, treedef) -> list:
        """Per-leaf NamedShardings of a flat state (structure `treedef`) on
        the engine's mesh: worker rows over the worker axes, the flat dim
        over the shard axes."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        sspec = flat.flat_state_specs(self.run_cfg, self.spec.worker_axes,
                                      self.spec)
        # PartitionSpec subclasses tuple (a pytree node): wrap in the opaque
        # NamedSharding so flatten_up_to treats each spec as one leaf
        ns = jax.tree.map(lambda s: NamedSharding(self.mesh, s), sspec,
                          is_leaf=lambda x: isinstance(x, P))
        return treedef.flatten_up_to(ns)

    def _keep_state_layout(self, fn):
        """Pin a mesh program's output state to the state's own shardings.
        Left to propagation, the consensus the sync all-gathers comes out
        replicated, and every device then holds every worker's params."""
        def pinned(state, *args):
            out = fn(state, *args)
            leaves, td = jax.tree.flatten(out[0])
            new = jax.tree.unflatten(td, [
                jax.lax.with_sharding_constraint(x, sh)
                for x, sh in zip(leaves, self._state_shardings(td))])
            return (new,) + tuple(out[1:])
        return pinned

    def params_single(self, state: Pytree) -> Pytree:
        """Worker-0 params as the model pytree, whatever the layout — the
        post-run handoff to eval/serving code."""
        if self._pending is not None:
            raise PendingSyncError(
                "in-flight sync: pass flush(state) or synced_view(state), "
                "not the raw run state")
        params = state["params"]
        if self.layout != "tree":
            params = self._ensure_spec().unflatten(params, lead=1)
        return jax.tree.map(lambda x: x[0], params)

    # -- compilation ------------------------------------------------------

    def _program(self, hp: int, apply_pending: bool = False):
        """Jitted round program for padded length hp.  Cache key: (hp, W) —
        a membership RESIZE moves W and so reaches fresh entries while the
        old-W programs stay parked for an instant revert; a pure mask
        change reuses the same program (membership is a traced argument).
        Overlap mode also keys on whether a pending sync is applied — the
        first round of a run has none — and on the overlap depth, so a
        controller retuning `set_overlap_depth` compiles at most one
        program per (bucket, depth) pair.  The adaptive batch lane count
        is a traced argument and never appears in the key."""
        key = ((hp, apply_pending, self.overlap_depth, self.workers)
               if self.sync_mode == "overlap" else (hp, self.workers))
        if key in self._programs:
            self.cache_hits += 1
            return self._programs[key]
        spec = self._ensure_spec() if self.layout != "tree" else None
        # without a mesh every worker replica lives on one device
        local = self.mesh is None
        if self.sync_mode == "overlap":
            fn = make_overlap_round(self.cfg, self.run_cfg, self._synth,
                                    spec, depth=self.overlap_depth,
                                    apply_pending=apply_pending,
                                    batch_arg=self.adaptive_batch,
                                    local_workers=local)
            donate = (0, 1) if apply_pending else (0,)
        elif self.sync_mode == "partial":
            fn = make_partial_round(self.cfg, self.run_cfg, self._synth,
                                    spec, batch_arg=self.adaptive_batch,
                                    local_workers=local)
            donate = (0,)
        elif self.mode == "bucketed":
            fn = make_bucketed_round(self.cfg, self.run_cfg, self._synth,
                                     spec, batch_arg=self.adaptive_batch,
                                     local_workers=local)
            donate = (0,)
        else:
            fn = make_exact_round(self.cfg, self.run_cfg, self._synth, spec,
                                  local_workers=local)
            donate = (0,)
        if self.mesh is not None:
            fn = self._keep_state_layout(fn)
        jit_kw = {"donate_argnums": donate} if self.donate else {}
        self._programs[key] = jax.jit(fn, **jit_kw)
        self._unlaunched[self._programs[key]] = key
        self.compiles += 1
        return self._programs[key]

    def compile_stats(self) -> dict:
        """compiles / cache_hits: `_program` builds and reuses; compile_s:
        host seconds in the first calls of new programs (trace, lower, and
        compile or persistent-cache load); compiled_at: (t, key) of the
        round each program was first called in."""
        return {"compiles": self.compiles, "cache_hits": self.cache_hits,
                "programs": sorted(self._programs),
                "compile_s": self.compile_s,
                "compiled_at": list(self.compiled_at)}

    # -- execution --------------------------------------------------------

    def run_round(self, state: Pytree, t: int, h: int, lr_fn):
        """Execute the communication round starting at step t with period h.

        Returns (state, metrics) where metrics holds device scalars
        {"loss", "grad_norm", "divergence"} computed in-graph.

        Host spans on the profiler's clock, each carrying t and h as
        arguments: `repro.engine.args` while the round's arguments are
        made, then `repro.engine.launch` around the jitted call —
        `repro.engine.compile` on a program's first call, whose seconds add
        to `compile_stats()["compile_s"]`.
        """
        span = jax.profiler.TraceAnnotation
        with span("repro.engine.args", t=t, h=h):
            fn, args = self._round_call(t, h, lr_fn)
        key = self._unlaunched.pop(fn, None)
        if key is None:
            with span("repro.engine.launch", t=t, h=h):
                out = fn(state, *args)
        else:
            t_call = time.perf_counter()
            with span("repro.engine.compile", t=t, h=h):
                out = fn(state, *args)
            self.compile_s += time.perf_counter() - t_call
            self.compiled_at.append((t, key))
        if self.sync_mode == "overlap":
            state, self._pending, metrics = out
        else:
            state, metrics = out
        self.h_trace.append((t, h))
        return state, metrics

    def compiled_round(self, state: Pytree, t: int, h: int, lr_fn):
        """The compiled program `run_round(state, t, h, lr_fn)` runs, for
        inspection (`memory_analysis()`, `as_text()`).  A program already
        compiled comes from JAX's compilation cache where one is on."""
        fn, args = self._round_call(t, h, lr_fn)
        return fn.lower(state, *args).compile()

    def _round_call(self, t: int, h: int, lr_fn):
        """(jitted program, its arguments after the state) for the round
        starting at step t with period h."""
        hp = bucket_pow2(h) if self.mode == "bucketed" else h
        # the schedule is only defined on [0, total_steps): query it for the
        # h valid steps and fill the hp - h padded lanes with the last valid
        # value.  Masked steps never apply an lr, but a decay schedule
        # queried past its domain can return negative/NaN values (or raise)
        # — the truncated final round must not poison the padded lanes
        lr_valid = [lr_fn(t + i) for i in range(h)]
        lrs = jnp.asarray(lr_valid + [lr_valid[-1]] * (hp - h), jnp.float32)
        fn = self._program(hp, self._pending is not None)
        args = []
        if self._synth is None:
            # only the h valid steps' batches are real; masked steps never
            # read theirs (lax.cond), so pad by repeating the last batch —
            # this skips the numpy synthesis of the hp - h pad batches (the
            # [Hp, ...] transfer itself is inherent to the fixed-shape
            # program)
            per_step = [self._host_batch(t + i) for i in range(h)]
            per_step += [per_step[-1]] * (hp - h)
            args.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_step))
        else:
            args.append(jnp.int32(t))
        args.append(lrs)
        if self.mode == "bucketed":
            args.append(jnp.arange(hp) < h)
        if self.adaptive_batch:
            args.append(jnp.int32(self.batch_lanes))
        if self.sync_mode == "partial":
            args.insert(0, jnp.asarray(self.membership, jnp.float32))
        if self.sync_mode == "overlap" and self._pending is not None:
            args.insert(0, self._pending)
        return fn, args

    def synced_view(self, state: Pytree) -> Pytree:
        """State with the in-flight sync applied, WITHOUT consuming it —
        the consensus an observer (eval, logging) should see mid-run under
        overlap mode.  Pure: the training trajectory is untouched."""
        if self._pending is None:
            return state
        if self._flush_fn is None:
            spec = self._ensure_spec() if self.layout != "tree" else None
            self._flush_fn = jax.jit(make_sync_apply(self.run_cfg, spec))
        return self._flush_fn(state, self._pending)

    def flush(self, state: Pytree) -> Pytree:
        """Apply the in-flight sync, if any (overlap mode): the pending
        reduce from the last round is gathered and applied exactly, leaving
        the state at the synced consensus a blocking round would have.  Call
        before checkpointing or reading out final params."""
        state = self.synced_view(state)
        self._pending = None
        return state

    # -- elastic membership -----------------------------------------------

    def membership_epoch(self, membership: Sequence[float] | None = None, *,
                         state: Pytree | None = None,
                         keep_lanes: Sequence[int] | None = None,
                         grow_to: int | None = None) -> Pytree | None:
        """The ONLY legal place the worker set changes — a round boundary.

        Three shapes of change, each recorded as a MembershipEpoch:

        * `membership_epoch([1, 1, 0, 1])` — participation mask for the
          next rounds (sync="partial" engines): lane 2 keeps training but
          its delta is excluded from the boundary mean, which divides by
          |P|=3.  W unchanged, nothing recompiles (the mask is traced).
        * `membership_epoch(state=st, keep_lanes=(0, 1, 3))` — lanes LEAVE:
          the worker axis shrinks to the kept lanes.  Returns the resized
          state; the flat spec is rebuilt and the (hp, W) compile cache
          reaches fresh entries while the old-W programs stay parked.
        * `membership_epoch(state=st, grow_to=4)` — lanes JOIN: new lanes
          clone lane 0 — the post-sync consensus params (re-anchoring, the
          ISSUE's rejoin rule) AND its optimizer moments (zeros would
          de-bias Adam against the shared step counter).

        Raises MembershipError with a sync in flight (the pending reduce
        was taken over the OLD membership), on an empty mask, or on a
        resize under a live mesh — `jax.distributed` process groups cannot
        shrink in place, so mesh worlds resize through the manifest
        checkpoint + respawn path (launch/multihost.py run_elastic), each
        OS-process generation being one epoch.
        """
        if self._pending is not None:
            raise MembershipError(
                "membership may only change at a round boundary: a sync is "
                "in flight over the old worker set — flush() first")
        resize = keep_lanes is not None or grow_to is not None
        if resize:
            if self.mesh is not None:
                raise MembershipError(
                    "mesh-backed engines resize via checkpoint + respawn "
                    "(launch/multihost.py run_elastic), not in place")
            if state is None:
                raise MembershipError("a resize needs the run state")
            if keep_lanes is not None:
                lanes = [int(i) for i in keep_lanes]
                if not lanes or not all(0 <= i < self.workers
                                        for i in lanes):
                    raise MembershipError(
                        f"keep_lanes {lanes} out of range for "
                        f"W={self.workers}")
            else:
                if grow_to <= self.workers:
                    raise MembershipError(
                        f"grow_to={grow_to} does not grow W={self.workers}")
                lanes = list(range(self.workers)) + \
                    [0] * (grow_to - self.workers)
            state = self._resize_lanes(state, lanes)
            self.membership = np.ones(self.workers, np.float32)
        elif membership is not None:
            mask = np.asarray(membership, np.float32)
            if mask.shape != (self.workers,) or mask.sum() < 1:
                raise MembershipError(
                    f"membership mask must be [{self.workers}] with at "
                    f"least one participant, got {mask!r}")
            self.membership = mask
        parked = tuple(k for k in self._programs
                       if k[-1] != self.workers) if resize else ()
        self.epochs.append(MembershipEpoch(
            index=len(self.epochs), workers=self.workers,
            membership=tuple(float(x) for x in self.membership),
            resized=resize, parked=parked))
        return state

    # -- adaptive round-boundary knobs -------------------------------------

    def batch_epoch(self, lanes: int) -> None:
        """The ONLY legal place the effective per-worker batch changes — a
        round boundary, mirroring membership_epoch.  `lanes` samples are
        consumed per step per worker from the next round on; the compiled
        batch shape stays b_loc (the lane count is a traced argument of
        every program — see data.synthetic.effective_batch_view), so the
        change costs ZERO recompiles beyond the existing H-bucket set.
        `lanes` must divide b_loc for the tiled mean to be an exact
        batch-`lanes` gradient."""
        if not self.adaptive_batch:
            raise MembershipError(
                "batch_epoch needs an adaptive_batch=True engine — the lane "
                "count is only a traced argument of adaptive programs")
        lanes = int(lanes)
        if not 1 <= lanes <= self.b_loc or self.b_loc % lanes:
            raise MembershipError(
                f"batch lanes must divide b_loc={self.b_loc} "
                f"(got {lanes})")
        self.batch_lanes = lanes
        self.batch_epochs.append(BatchEpoch(
            index=len(self.batch_epochs), lanes=lanes, b_loc=self.b_loc,
            round_index=len(self.h_trace)))

    def set_overlap_depth(self, depth: int) -> None:
        """Retune --overlap-depth at a round boundary (overlap engines
        only).  Depth is a compile-cache key component, so each (bucket,
        depth) pair compiles at most once and revisited depths are cache
        hits."""
        if self.sync_mode != "overlap":
            raise MembershipError(
                "overlap depth is only a knob under --sync overlap")
        depth = int(depth)
        if depth < 0:
            raise MembershipError(f"overlap depth must be >= 0, got {depth}")
        self.overlap_depth = depth

    def _resize_lanes(self, state: Pytree, lanes: list[int]) -> Pytree:
        """Re-pad the worker axis to `lanes` (source lane per new slot),
        through the tree layout as the common currency — exactly the
        cross-layout restore route, so the kept lanes stay bitwise.  The
        flat spec, batch synthesizer, and flush program are all rebuilt
        for the new W."""
        spec = self._ensure_spec() if self.layout != "tree" else None
        tree_state = (state if spec is None
                      else flat.to_tree_state(spec, state))
        tree_state = _remap_worker_lanes(tree_state, lanes)
        self.workers = len(lanes)
        self.spec = None
        self._flush_fn = None
        if self.data == "device":
            self._synth = device_batch_fn(self.cfg, self.stream,
                                          self.workers, self.b_loc, self.seq)
        if self.layout == "tree":
            return tree_state
        params_single = jax.tree.map(lambda x: x[0], tree_state["params"])
        return flat.to_flat_state(self._ensure_spec(params_single),
                                  tree_state)

    # -- checkpointing ----------------------------------------------------

    def checkpoint_extra(self) -> dict:
        """The engine-side checkpoint metadata: the H-trace (resume lands on
        a round boundary) + the param-layout record for cross-layout
        restore.  Exposed so async observers (core/observer.py) can capture
        it on the round loop's thread at snapshot time — the trace keeps
        advancing while the background writer runs."""
        spec = self._ensure_spec() if self.layout != "tree" else None
        return {"h_trace": [[t, h] for t, h in self.h_trace],
                "workers": self.workers,
                **ckpt_io.layout_meta(self.layout, spec)}

    def save(self, path: str, state: Pytree, *, step: int,
             flush_pending: bool = False) -> None:
        """Checkpoint state + the engine's step / H-trace so a resumed run
        lands exactly on the next round boundary.  Flat layouts checkpoint
        the buffers directly — one entry per dtype bucket, not per tensor —
        with the layout recorded in the meta side file for cross-layout
        restore (checkpoint/io.py).

        Overlap mode: a checkpoint written mid-overlap must never hold
        pre-consensus params.  With a sync in flight this raises
        PendingSyncError (a real error, not a stripped-under-`python -O`
        assert) unless `flush_pending=True`, which writes the *synced view*
        of `state` — the consensus a blocking round would have produced —
        WITHOUT consuming the in-flight pipeline, so the training stream
        continues overlapped.  `flush()` + save remains the forced-sync
        alternative."""
        if self._pending is not None:
            if not flush_pending:
                raise PendingSyncError(
                    "overlap sync in flight: save(flush_pending=True) "
                    "writes the synced consensus without disturbing the "
                    "pipeline, or flush() first for a forced sync point")
            state = self.synced_view(state)
        ckpt_io.save(path, state, step=step, extra=self.checkpoint_extra())

    def restore(self, path: str, like_state: Pytree) -> tuple[Pytree, int]:
        """Restore into this engine's layout.  A checkpoint written under
        any other param layout (tree <-> flat <-> flat_sharded, or a
        different shard count) is converted on the way in through the tree
        layout as the common currency — flatten/unflatten are exact, so
        resuming across layouts stays bitwise-faithful.

        Refuses a live in-flight sync: restoring over it would silently
        orphan a round's reduce — flush() (or discard the run) first."""
        if self._pending is not None:
            raise PendingSyncError(
                "restore() with an overlap sync in flight would orphan the "
                "pending reduce: flush() the current state first")
        _, meta = ckpt_io.read_meta(path)
        ck_layout = meta.get("layout", "tree")
        ck_shards = meta.get("shards")
        my_shards = (self._ensure_spec().shards
                     if self.layout == "flat_sharded" else None)
        convert = ck_layout != self.layout or ck_shards != my_shards
        ck_spec = None
        if convert:
            # tree-layout engines derive the spec from the live state (its
            # dtypes are authoritative); flat engines already carry one
            tree_state = (like_state if self.layout == "tree"
                          else flat.to_tree_state(self._ensure_spec(),
                                                  like_state))
            if ck_layout == "tree":
                like = tree_state
            else:
                params_single = jax.tree.map(lambda x: x[0],
                                             tree_state["params"])
                ck_spec = (flat.ShardedFlatSpace(params_single,
                                                 ck_shards or 1)
                           if ck_layout == "flat_sharded"
                           else flat.FlatParamSpace(params_single))
                like = flat.to_flat_state(ck_spec, tree_state)
        else:
            like = like_state
        state, step, extra = ckpt_io.restore_with_meta(path, like)
        if convert:
            if ck_spec is not None:
                state = flat.to_tree_state(ck_spec, state)
            if self.layout != "tree":
                state = flat.to_flat_state(self._ensure_spec(), state)
        return state, self._adopt_trace(extra, step)

    def _adopt_trace(self, extra: dict, step) -> int:
        trace = [(int(t), int(h)) for t, h in extra.get("h_trace", [])]
        step = int(step or 0)
        if trace:
            done = trace[-1][0] + trace[-1][1]
            if done != step:     # real error: survives `python -O`
                raise ValueError(
                    f"checkpoint step {step} is not the round boundary "
                    f"implied by its H-trace (ends at {done})")
        self.h_trace = trace
        return step

    def save_sharded(self, path: str, state: Pytree, *, step: int,
                     flush_pending: bool = False, barrier=None) -> None:
        """Per-host shard-file checkpoint (checkpoint/io.py save_sharded):
        this process writes ONLY its addressable shards; process 0 adds the
        manifest naming every shard file.  `barrier` (a zero-arg callable,
        e.g. a cross-process sync) runs after the shard files are durable
        and before the manifest is written, so a manifest never names a
        file that doesn't exist yet.  Same PendingSyncError contract as
        `save`."""
        if self._pending is not None:
            if not flush_pending:
                raise PendingSyncError(
                    "overlap sync in flight: save_sharded(flush_pending="
                    "True) writes the synced consensus without disturbing "
                    "the pipeline, or flush() first")
            state = self.synced_view(state)
        ckpt_io.save_sharded(path, state, step=step,
                             extra=self.checkpoint_extra(), barrier=barrier)

    def restore_elastic(self, path: str, like_state: Pytree) -> tuple[Pytree, int]:
        """Restore a checkpoint written under ANY worker count — and any
        layout / shard count / process count, manifest or monolithic —
        into this engine.  Writer lanes beyond this engine's W are dropped
        (highest first); missing lanes clone the checkpoint's lane 0: at a
        round boundary every *participating* lane holds the post-sync
        consensus, so the clone IS the re-anchoring rule a rejoining
        worker needs (params and moments both — zero moments would
        de-bias Adam against the shared step counter).

        The lane remap runs through the tree layout exactly like the
        cross-layout `restore` route, so surviving lanes stay bitwise."""
        if self._pending is not None:
            raise PendingSyncError(
                "restore_elastic() with an overlap sync in flight would "
                "orphan the pending reduce: flush() first")
        # the writer-geometry `like` built below only needs SHAPES: rebuild
        # the template from host zeros so the lane remap never issues an
        # eager cross-device gather on mesh-global state — under gloo that
        # gather deadlocks whenever one process owns more than one device
        # (2 procs x 2 devices, say).  The restore itself is host-side
        # anyway, and _to_global lays the result back onto the mesh.
        like_state = jax.tree.map(
            lambda x: (np.zeros(x.shape, x.dtype)
                       if isinstance(x, (jax.Array, np.ndarray)) else x),
            like_state)
        manifest = ckpt_io.is_manifest(path)
        _, extra = (ckpt_io.read_manifest_meta(path) if manifest
                    else ckpt_io.read_meta(path))
        ck_layout = extra.get("layout", "tree")
        ck_shards = extra.get("shards")
        ck_w = int(extra.get("workers") or self.workers)
        # a like tree in the WRITER's geometry, built from this engine's
        # state: lanes remapped to ck_w (shapes are all that matter here),
        # then laid out as the writer's layout
        my_tree = (like_state if self.layout == "tree"
                   else flat.to_tree_state(self._ensure_spec(), like_state))
        to_ck = (list(range(ck_w)) if ck_w <= self.workers
                 else list(range(self.workers)) + [0] * (ck_w - self.workers))
        ck_tree = _remap_worker_lanes(my_tree, to_ck)
        ck_spec = None
        if ck_layout != "tree":
            params_single = jax.tree.map(lambda x: x[0], ck_tree["params"])
            ck_spec = (flat.ShardedFlatSpace(params_single, ck_shards or 1)
                       if ck_layout == "flat_sharded"
                       else flat.FlatParamSpace(params_single))
        like = (ck_tree if ck_spec is None
                else flat.to_flat_state(ck_spec, ck_tree))
        rest = (ckpt_io.restore_sharded if manifest
                else ckpt_io.restore_with_meta)
        state, step, extra = rest(path, like)
        if ck_spec is not None:
            state = flat.to_tree_state(ck_spec, state)
        back = (list(range(self.workers)) if ck_w >= self.workers
                else list(range(ck_w)) + [0] * (self.workers - ck_w))
        state = _remap_worker_lanes(state, back)
        if self.layout != "tree":
            state = flat.to_flat_state(self._ensure_spec(), state)
        if self.mesh is not None:
            state = self._to_global(state)
        self.membership = np.ones(self.workers, np.float32)
        return state, self._adopt_trace(extra, step)
