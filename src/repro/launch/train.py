"""Training driver: a thin host loop over `repro.core.engine.RoundEngine`.

The engine owns compilation (power-of-two H-bucketed compile cache —
O(log H_max) XLA programs for a full QSR schedule instead of one per
distinct H), buffer donation, in-graph telemetry (loss / grad norm / worker
divergence), and the data path (on-device fold_in batch synthesis by
default; `--data host` for the numpy stream).  This file only walks the
H-schedule: ask `schedules.get_h` for the next round's period, hand the
round to the engine, log, checkpoint.

Both of the paper's algorithms run through the same engine: Local OPT with
any H-schedule (Alg. 2) and the data-parallel baseline (Alg. 1 ==
`--schedule parallel`, i.e. H=1 every round).  `--engine legacy` is the
escape hatch back to one-compile-per-distinct-H exact rounds.

  PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b --smoke \
      --schedule qsr --steps 200 --workers 4
"""
from __future__ import annotations

import argparse
import time

from repro.checkpoint import io as ckpt_io
from repro.configs.base import RunConfig
from repro.core import schedules
from repro.core.engine import RoundEngine
from repro.errors import ConfigError
from repro.optim.lr import make_lr_fn


def train(cfg, run_cfg: RunConfig, *, workers: int, b_loc: int, seq: int,
          seed: int = 0, ckpt_dir: str | None = None, log_every: int = 1,
          engine: str = "bucketed", data: str = "device",
          layout: str = "tree", sync: str = "blocking",
          overlap_depth: int = 0, eval_fn=None,
          async_observer: bool = False,
          eng: RoundEngine | None = None,
          controller_trace: str | None = None, frontier=None):
    """Run a full training run; returns (state, history).

    history rows are (t_end, h, loss, lr) — unchanged from the pre-engine
    driver so downstream plots/tests keep working.  Pass an `eng` to keep a
    handle on the engine (compile stats, H-trace) after the run; otherwise
    one is built from the `engine`/`data`/`layout`/`sync` mode flags.
    With sync="overlap" the in-flight reduce is flushed at checkpoints and
    before returning, so the returned state is always the synced consensus.

    schedule="adaptive" swaps the open-loop `schedules.get_h` walk for a
    core/controller.py AdaptiveController around every round: H gets a
    divergence correction on top of the QSR prior, the effective per-worker
    batch grows through zero-recompile `batch_epoch`s (engines built with
    `adaptive_batch=True` — automatic here under the bucketed engine), and
    with sync="overlap" + a `frontier` ({depth: s/round} dict or a
    table4_walltime JSON path) the overlap depth rides the walltime
    frontier.  `controller_trace` names a JSON file to persist the
    per-round decision stream (schema controller_trace/v1).

    async_observer=True moves eval and mid-run checkpoints off the round
    loop: the engine's synced_view (pure — the overlap pipeline is
    untouched) is submitted to a background AsyncObserver worker
    (core/observer.py) that device_gets and runs `eval_fn` / writes the
    checkpoint on a host thread, double-buffered so the training stream
    never blocks on observer I/O.  Mid-run checkpoints are then written
    from the consensus view WITHOUT forcing a sync point; the final
    checkpoint is still written synchronously after the run's flush.
    """
    adaptive = run_cfg.schedule == "adaptive"
    if eng is None:
        eng = RoundEngine(cfg, run_cfg, workers=workers, b_loc=b_loc,
                          seq=seq, seed=seed, mode=engine, data=data,
                          layout=layout, sync=sync,
                          overlap_depth=overlap_depth,
                          adaptive_batch=adaptive and engine == "bucketed")
    else:
        got = (eng.cfg, eng.run_cfg, eng.workers, eng.b_loc, eng.seq,
               eng.seed, eng.mode, eng.data, eng.layout, eng.sync_mode,
               eng.overlap_depth)
        want = (cfg, run_cfg, workers, b_loc, seq, seed, engine, data,
                layout, sync, overlap_depth)
        if got != want:
            raise ConfigError(
                "engine built with (cfg, run_cfg, workers, b_loc, seq, seed, "
                f"mode, data, layout, sync, overlap_depth)={got},\n"
                f"train() called with {want}")
    state = eng.init_state()
    lr_fn = make_lr_fn(run_cfg)

    ctrl = None
    if adaptive:
        from repro.core.controller import AdaptiveController, load_frontier
        if isinstance(frontier, str):
            frontier = load_frontier(frontier)
        ctrl = AdaptiveController(run_cfg, lr_fn, engine=eng,
                                  frontier=frontier)

    step0 = 0
    if ckpt_dir and ckpt_io.exists(ckpt_dir):
        state, step0 = eng.restore(ckpt_dir, state)
        print(f"restored checkpoint at round boundary {step0} "
              f"({len(eng.h_trace)} rounds done)")

    observer = None
    if async_observer and (eval_fn is not None or ckpt_dir):
        from repro.core.observer import AsyncObserver

        def handle(step, snap):
            # worker thread: snap is the staged (host) consensus view
            if eval_fn is not None:
                eval_fn(step, snap["state"])
            if snap.get("save"):
                ckpt_io.save(ckpt_dir, snap["state"], step=step,
                             extra=snap["extra"])
        # a superseded snapshot's checkpoint request rides the newer one
        # (the newer consensus is a strictly better checkpoint)
        observer = AsyncObserver(
            handle, merge=lambda old, new: ({**new, "save": True}
                                            if old.get("save") else new))

    history = []
    t_start = time.time()
    t = saved_at = step0
    while t < run_cfg.total_steps:
        h = (ctrl.begin_round(t) if ctrl is not None
             else schedules.get_h(run_cfg, t, lr_fn))
        state, m = eng.run_round(state, t, h, lr_fn)
        if ctrl is not None:
            ctrl.end_round(t, h, m)
        t += h
        loss = float(m["loss"])
        history.append((t, h, loss, lr_fn(t - 1)))
        if log_every and (len(history) % log_every == 0):
            cs = eng.compile_stats()
            print(f"step {t:6d}  H {h:4d}  lr {lr_fn(t-1):.5f}  "
                  f"loss {loss:.4f}  |g| {float(m['grad_norm']):.3f}  "
                  f"div {float(m['divergence']):.4f}  "
                  f"compiles {cs['compiles']} (hits {cs['cache_hits']})  "
                  f"({time.time()-t_start:.1f}s)")
        want_ckpt = bool(ckpt_dir) and \
            t % max(run_cfg.total_steps // 4, 1) == 0
        if observer is not None:
            if eval_fn is not None or want_ckpt:
                # overlap mode: observers see the synced consensus (pure
                # view; the in-flight pipeline is untouched), so eval curves
                # and checkpoints match blocking-sync runs — device_get and
                # I/O happen on the observer thread, not here
                snap = eng.synced_view(state)
                if snap is state and eng.donate:
                    # blocking sync: the view IS the live state, whose
                    # buffers the next round donates — give the observer
                    # its own copy (async device op, no host sync)
                    import jax
                    import jax.numpy as jnp
                    snap = jax.tree.map(jnp.copy, state)
                observer.submit(t, {"state": snap, "save": want_ckpt,
                                    "extra": eng.checkpoint_extra()})
                if want_ckpt:
                    saved_at = t
        else:
            if eval_fn is not None:
                eval_fn(t, eng.synced_view(state))
            if want_ckpt:
                # overlap mode: a checkpoint is a forced sync point — the
                # in-flight reduce is applied so the saved state is a round
                # boundary in the blocking sense
                state = eng.flush(state)
                eng.save(ckpt_dir, state, step=t)
                saved_at = t
    state = eng.flush(state)
    if observer is not None:
        observer.close()
    if ckpt_dir and saved_at != t:
        eng.save(ckpt_dir, state, step=t)
    if ctrl is not None and controller_trace:
        ctrl.write_trace(controller_trace)
        print(f"controller trace ({len(ctrl.trace)} rounds) -> "
              f"{controller_trace}")
    return state, history


def main():
    from repro.launch import multihost
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    distributed = multihost.initialize()  # no-op without REPRO_COORDINATOR
    if distributed:
        print(f"multihost: {multihost.runtime_info()}")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    # choices derive from the schedules module so CLI and core cannot drift
    ap.add_argument("--schedule", default="qsr",
                    choices=list(schedules.SCHEDULE_KINDS))
    ap.add_argument("--engine", default="bucketed",
                    choices=["bucketed", "legacy"],
                    help="bucketed: pow2 compile cache; legacy: per-H jit")
    ap.add_argument("--data", default="device", choices=["device", "host"],
                    help="batch synthesis inside the jitted round vs numpy")
    ap.add_argument("--param-layout", default="tree",
                    choices=["tree", "flat", "flat_sharded"],
                    help="tree: state mirrors the model pytree (per-tensor "
                         "stats); flat: dtype-bucketed 1-D buffers — one "
                         "sync all-reduce and one optimizer kernel per "
                         "bucket (core/flat.py), bitwise-equal training; "
                         "flat_sharded: buckets padded into per-device "
                         "contiguous chunks (FSDP-style) — sync decomposes "
                         "into reduce_scatter + all_gather, bitwise-equal "
                         "too")
    ap.add_argument("--sync", default="blocking",
                    choices=["blocking", "overlap", "partial"],
                    help="blocking: each round ends fully synced (Alg. 1/2 "
                         "verbatim); overlap: the delta reduce is issued at "
                         "the round boundary and the gather/apply deferred "
                         "past the next round's first --overlap-depth local "
                         "steps (depth 0 keeps the blocking trajectory "
                         "bitwise); partial: elastic rounds averaging over "
                         "the engine's per-round membership mask only "
                         "(all-present == blocking; see README §Elastic "
                         "training)")
    ap.add_argument("--overlap-depth", type=int, default=0,
                    help="local steps the next round runs on stale params "
                         "before the deferred sync applies (--sync overlap)")
    ap.add_argument("--mesh", default=None,
                    help="run the rounds on a device mesh, e.g. 4x2 (data x "
                         "model) or 2x2x2 (pod x data x model): requires "
                         "--param-layout flat_sharded; the sync then "
                         "executes its explicit reduce_scatter/all_gather "
                         "collectives — across processes when launched "
                         "under jax.distributed (launch/multihost.py).  "
                         "--workers must equal the policy's worker count "
                         "on the mesh")
    ap.add_argument("--policy", default="dp", choices=["dp", "fsdp"],
                    help="sharding policy naming the mesh's worker axes "
                         "(dp: every data rank; fsdp: one worker per pod)")
    ap.add_argument("--async-observer", action="store_true",
                    help="run eval + mid-run checkpoints on a background "
                         "host thread fed by the engine's synced_view "
                         "(core/observer.py): device_get and checkpoint "
                         "I/O leave the round loop's critical path, "
                         "double-buffered so training never blocks")
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgd"])
    ap.add_argument("--quantize", action="store_true",
                    help="int8-quantized sync deltas (README §Quantized "
                         "sync); implied by --wire ring-int8")
    ap.add_argument("--wire", default="auto", choices=["auto", "ring-int8"],
                    help="quantized payload wire mode (README §Wire modes): "
                         "auto = exact int16/int32 code-sums; ring-int8 = "
                         "re-quantizing int8 ppermute ring (needs "
                         "--param-layout flat|flat_sharded)")
    ap.add_argument("--controller-trace", default=None,
                    help="--schedule adaptive: JSON path for the per-round "
                         "controller decision stream (schema "
                         "controller_trace/v1; README §Adaptive controller)")
    ap.add_argument("--frontier", default=None,
                    help="--schedule adaptive + --sync overlap: "
                         "table4_walltime JSON whose measured s/round rows "
                         "give the overlap-depth walltime frontier the "
                         "controller chooses depth on")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8, help="per-worker batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--peak-lr", type=float, default=3e-3)
    ap.add_argument("--alpha", type=float, default=0.002)
    ap.add_argument("--h-base", type=int, default=2)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    from repro.configs import registry as R
    cfg = R.get_smoke_config(args.arch) if args.smoke else R.get_config(args.arch)
    run_cfg = RunConfig(
        schedule=args.schedule, optimizer=args.optimizer, sharding=args.policy,
        total_steps=args.steps, peak_lr=args.peak_lr, alpha=args.alpha,
        h_base=args.h_base, warmup_steps=max(args.steps // 20, 1),
        remat=False,
        sync_quantize=args.quantize or args.wire == "ring-int8",
        sync_wire=args.wire)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(*multihost._parse_mesh(args.mesh))
    eng = RoundEngine(cfg, run_cfg, workers=args.workers, b_loc=args.batch,
                      seq=args.seq, mode=args.engine, data=args.data,
                      layout=args.param_layout, sync=args.sync,
                      overlap_depth=args.overlap_depth,
                      mesh=mesh, policy=args.policy,
                      adaptive_batch=(args.schedule == "adaptive"
                                      and args.engine == "bucketed"))
    state, hist = train(cfg, run_cfg, workers=args.workers, b_loc=args.batch,
                        seq=args.seq, ckpt_dir=args.ckpt, engine=args.engine,
                        data=args.data, layout=args.param_layout,
                        sync=args.sync, overlap_depth=args.overlap_depth,
                        async_observer=args.async_observer, eng=eng,
                        controller_trace=args.controller_trace,
                        frontier=args.frontier)
    losses = [l for _, _, l, _ in hist]
    if not losses:
        print("nothing to do: checkpoint already at "
              f"step {run_cfg.total_steps}")
        return
    n_sync = len(hist)
    cs = eng.compile_stats()
    print(f"\nfinal loss {losses[-1]:.4f}  (first {losses[0]:.4f}); "
          f"{n_sync} communication rounds for {args.steps} steps "
          f"(comm volume {n_sync/args.steps:.1%} of data-parallel); "
          f"{cs['compiles']} XLA round programs "
          f"(buckets {cs['programs']}, {cs['cache_hits']} cache hits, "
          f"{cs['compile_s']:.1f}s compiling)")


if __name__ == "__main__":
    main()
