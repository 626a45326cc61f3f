"""Local-gradient runtime (paper Alg. 2) + the data-parallel baseline (Alg. 1).

Worker replicas are an explicit leading axis `W` on params/optimizer state,
sharded over the worker mesh axes (DESIGN.md §2) so replicas diverge between
syncs.  A local step is a vmapped per-worker loss/grad + an elementwise
optimizer update (no cross-worker collective by construction); sync is a
W-axis mean -> one all-reduce every H steps.  `train_round` fuses H local
steps (lax.scan) + sync into one jitted program — the unit the dry-run lowers.

Param layouts: by default state mirrors the model pytree; with a
`core.flat.FlatParamSpace` the same runtime carries params/optimizer state
as a few dtype-bucketed [W, N] buffers (see core/flat.py) — one collective
per bucket at sync, one fused optimizer kernel per bucket per step.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.sync import make_sync, worker_mean
from repro.models.common import scan_unroll
from repro.models import api
from repro.optim.optimizers import make_optimizer

Pytree = Any


def replicate_for_workers(tree: Pytree, w: int) -> Pytree:
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (w,) + x.shape), tree)


def init_state(cfg, run_cfg, params_single: Pytree, w: int) -> Pytree:
    """Build runtime state with a leading worker axis W."""
    opt = make_optimizer(run_cfg)
    params = replicate_for_workers(params_single, w)
    state = {"params": params, "opt": opt.init(params)}
    if run_cfg.sync_quantize or run_cfg.outer_momentum > 0.0:
        state["anchor"] = params_single
        if run_cfg.outer_momentum > 0.0:
            state["outer_mu"] = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params_single)
    return state


def make_loss(cfg, run_cfg):
    mod = api.get_module(cfg)
    if cfg.n_experts:
        from repro.models import moe as _moe
        _moe.set_dispatch_shards(getattr(run_cfg, "moe_dispatch_shards", 1))
        mode = getattr(run_cfg, "moe_dispatch", "auto")
        _moe.set_dispatch(mode, _moe._DISPATCH_MESH)
    remat = run_cfg.remat
    pol = getattr(run_cfg, "remat_policy", "full")
    if remat and pol in ("save_collectives", "dots"):
        remat = pol
    kw = {}
    if (getattr(run_cfg, "seq_shard_activations", False)
            and cfg.family in ("dense", "moe", "vlm")):
        from jax.sharding import PartitionSpec as P

        def con(h):  # [B, S, D] inside the per-worker vmap
            try:
                return jax.lax.with_sharding_constraint(
                    h, P(None, "model", None))
            except Exception:
                return h  # no mesh in scope (single-device CPU tests)
        kw["act_constraint"] = con
    return partial(mod.loss_fn, cfg, remat=remat, **kw)


def make_local_step(cfg, run_cfg, *, with_metrics: bool = False, spec=None,
                    local_workers: bool = False):
    """One per-worker optimizer step: NO cross-worker communication.

    state leaves have leading worker axis W; batch leaves have leading W.
    With `with_metrics=True` the step returns (state, (loss, grad_norm))
    where grad_norm is the worker-mean global gradient L2 norm — computed
    in-graph so the RoundEngine can log it without a second backward pass.

    With `spec` (a core.flat.FlatParamSpace) params/opt are flat dtype
    buckets {bucket: [W, N]}: the loss sees the unflattened view (pure
    slices/reshapes) and gradients are taken w.r.t. the flat buffers
    directly — the transpose of a slice is a disjoint scatter, so each
    element's gradient is bitwise the per-leaf gradient — and the optimizer
    runs one fused update per bucket instead of one per leaf.
    `local_workers=True` says every worker replica lives on one device: the
    views are then sliced and the per-leaf gradients concatenated worker by
    worker (`spec.worker_views` / `worker_buffers`), the same values through
    ops the TPU compiler handles in seconds rather than minutes at
    published widths.
    """
    tree_loss_fn = make_loss(cfg, run_cfg)
    tree_grad_fn = jax.value_and_grad(tree_loss_fn)
    if spec is None:
        grad_fn = tree_grad_fn
    else:
        grad_fn = jax.value_and_grad(
            lambda bufs, batch: tree_loss_fn(spec.unflatten(bufs), batch))
    opt = make_optimizer(run_cfg)

    mb = getattr(run_cfg, "microbatch", 1)

    @jax.named_scope("grad")
    def _value_and_grad(grad_fn, params, batch):
        """Per-worker loss/grad, optionally microbatched (grad accumulation
        over `mb` sequential chunks — peak activation memory / mb).  Runs
        under the `grad` scope, so a profile can tell the local step's
        forward and backward (`transpose(...)` in an op's path) apart."""
        if mb <= 1:
            return grad_fn(params, batch)
        chunks = jax.tree.map(
            lambda x: x.reshape((mb, x.shape[0] // mb) + x.shape[1:]), batch)

        def body(acc, chunk):
            loss, g = grad_fn(params, chunk)
            acc_loss, acc_g = acc
            return (acc_loss + loss / mb,
                    jax.tree.map(lambda a, b: a + b / mb, acc_g, g)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params))
        (loss, grads), _ = jax.lax.scan(body, zero, chunks,
                                        unroll=scan_unroll())
        return loss, grads

    def local_step(state, batch, lr):
        w = jax.tree.leaves(batch)[0].shape[0]
        if w == 1:
            # single replica (fsdp pod-worker): skip vmap so explicit
            # shard_map regions (MoE dispatch) can run inside the loss
            loss, g = _value_and_grad(
                grad_fn, jax.tree.map(lambda x: x[0], state["params"]),
                jax.tree.map(lambda x: x[0], batch))
            losses = loss[None]
            grads = jax.tree.map(lambda x: x[None], g)
        elif spec is not None and local_workers:
            losses, tree_grads = jax.vmap(partial(_value_and_grad,
                                                  tree_grad_fn))(
                spec.worker_views(state["params"]), batch)
            grads = spec.worker_buffers(tree_grads)
        else:
            losses, grads = jax.vmap(partial(_value_and_grad, grad_fn))(
                state["params"], batch)
        # optimizer update is elementwise -> applies across the W axis as-is
        with jax.named_scope("optimizer"):
            params, opt_state = opt.update(state["params"], state["opt"],
                                           grads, lr)
        new_state = {**state, "params": params, "opt": opt_state}
        if not with_metrics:
            return new_state, jnp.mean(losses)
        with jax.named_scope("telemetry"):
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)),
                             axis=tuple(range(1, g.ndim)))
                     for g in jax.tree.leaves(grads))       # [W]
            gn = jnp.mean(jnp.sqrt(sq))
        return new_state, (jnp.mean(losses), gn)

    return local_step


def make_train_round(cfg, run_cfg):
    """(state, batches [H,W,...], lrs [H]) -> (state, mean_loss).

    The paper-faithful communication round: H local steps, then one
    parameter-average sync."""
    local_step = make_local_step(cfg, run_cfg)
    sync = make_sync(run_cfg)

    def round_fn(state, batches, lrs):
        def body(st, xs):
            batch, lr = xs
            st, loss = local_step(st, batch, lr)
            return st, loss

        state, losses = jax.lax.scan(body, state, (batches, lrs),
                                     unroll=scan_unroll())
        return sync(state), jnp.mean(losses)

    return round_fn


def make_parallel_step(cfg, run_cfg):
    """Data-parallel baseline (paper Alg. 1): gradients are averaged over the
    global batch every step (GSPMD inserts the gradient all-reduce).

    state has NO worker axis; batch leaves are [B_global, ...] sharded over
    the data axes."""
    loss_fn = make_loss(cfg, run_cfg)
    opt = make_optimizer(run_cfg)

    def step(state, batch, lr):
        with jax.named_scope("grad"):
            loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        with jax.named_scope("optimizer"):
            params, opt_state = opt.update(state["params"], state["opt"],
                                           grads, lr)
        return {"params": params, "opt": opt_state}, loss

    return step


def init_parallel_state(cfg, run_cfg, params_single: Pytree) -> Pytree:
    opt = make_optimizer(run_cfg)
    return {"params": params_single, "opt": opt.init(params_single)}
