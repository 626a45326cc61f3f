"""The reference of a QSR training round: W workers, each taking H local
AdamW steps on its own batches from one shared start, then the plain mean
of their parameters; optimizer moments stay with their worker (paper
Alg. 2, Local AdamW).  Worker by worker and step by step, in the numerics
the caller chooses, with the learning rate of the schedule the traffic
file states.

It reads the three things the benchmark compares: each round's mean loss,
the per-leaf norm of every worker's first moment after the first round
(the gradient as the optimizer got it), and the per-leaf norm of every
worker's parameter change after the last round read.

`faults` plants what the benchmark has to catch, for the calibration of
its limits: "half_batch" takes each worker's loss over the first half of
its batch (of its tokens, where a worker holds one sequence); "no_sync"
leaves out the mean over workers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import common as C

FAULTS = ("half_batch", "no_sync")


def lr_at(sched: dict, t: int) -> float:
    """Linear warm-up, then cosine decay from peak_lr to end_lr."""
    peak, end = sched["peak_lr"], sched["end_lr"]
    warm, total = sched["warmup_steps"], sched["total_steps"]
    if warm and t < warm:
        return peak * (t + 1) / warm
    frac = min(max(t - warm, 0) / max(total - warm, 1), 1.0)
    return end + 0.5 * (peak - end) * (1 + math.cos(math.pi * frac))


def qsr_h(sched: dict, t: int) -> int:
    """The QSR period of the round starting at step t (paper eq. 2), with
    the learning rate pinned to its post-warm-up value during warm-up."""
    eta = lr_at(sched, max(t, sched["warmup_steps"]))
    h = max(sched["h_base"], int((sched["alpha"] / eta) ** 2))
    return max(1, min(h, sched["total_steps"] - t))


def half_batch(batch: dict) -> dict:
    """The first half of one worker's batch: of its rows, or of its
    positions where it holds a single row."""
    b = jax.tree.leaves(batch)[0].shape[0]
    if b > 1:
        return jax.tree.map(lambda x: x[: b // 2], batch)
    return jax.tree.map(lambda x: x[:, : x.shape[1] // 2], batch)


@jax.jit
def _one_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _change_norms(p, p0):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b)))
            for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0))]


def _by_leaf(tree, per_worker: list) -> dict[str, np.ndarray]:
    """{leaf path: [W]} from one list of per-leaf norms per worker."""
    return {p: np.asarray([float(n[i]) for n in per_worker], np.float64)
            for i, p in enumerate(C.leaf_paths(tree))}


class Reference:
    """One model under one traffic mix, W workers, in numerics `num`, with
    `faults` planted; its jitted step is built once and reused by `run`.
    Worker w's state lives on devices[w % len(devices)]; the mean over
    workers is taken on devices[0]."""

    def __init__(self, model, conf: dict, traffic: dict, workers: int,
                 num: C.Numerics = C.FLOAT32, faults: tuple[str, ...] = (),
                 devices=None):
        unknown = set(faults) - set(FAULTS)
        if unknown:
            raise ValueError(f"unknown faults {sorted(unknown)}")
        self.sched = traffic["schedule"]
        self.workers, self.num, self.faults = workers, num, tuple(faults)
        devices = devices or jax.devices()[:1]
        self.devices = [devices[w % len(devices)] for w in range(workers)]
        opt = traffic["optimizer"]
        b1, b2, eps, wd = (opt["beta1"], opt["beta2"], opt["eps"],
                           opt["weight_decay"])
        take = half_batch if "half_batch" in faults else (lambda b: b)

        def step(p, m, v, batch, lr, k):
            loss, g = jax.value_and_grad(
                lambda q: model.loss(conf, q, take(batch), num))(p)

            def adamw(p, m, v, g):
                g = g.astype(jnp.float32)
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * g * g
                upd = (m / (1 - b1 ** k)) / (jnp.sqrt(v / (1 - b2 ** k))
                                             + eps)
                pf = p.astype(jnp.float32)
                return (pf - lr * (upd + wd * pf)).astype(p.dtype), m, v

            out = jax.tree.map(adamw, p, m, v, g)
            pick = lambda i: jax.tree.map(
                lambda o: o[i], out, is_leaf=lambda x: isinstance(x, tuple))
            return loss, pick(0), pick(1), pick(2)

        self._step = jax.jit(step, donate_argnums=(0, 1, 2))
        self._mean = jax.jit(lambda trees: jax.tree.map(
            lambda *xs: (sum(x.astype(jnp.float32) for x in xs)
                         / len(xs)).astype(xs[0].dtype), *trees))
        self._start = jax.jit(lambda p: (
            jax.tree.map(lambda x: x.astype(num.dtype), p),
            jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p),
            jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)))

    def run(self, params0, pool: list, *, t0: int, rounds: int) -> dict:
        """`rounds` rounds from step t0 on the pool's batches (pool[i] is
        step t0 + i's batch, leaves [W, B, ...]).  Returns the readings:
        {"losses": [per round], "grad": {leaf: [W]}, "change": {leaf: [W]}}.
        """
        w_n, devs = self.workers, self.devices
        p0s = [jax.device_put(params0, d) for d in devs]
        ps, ms, vs = map(list, zip(*[self._start(p) for p in p0s]))
        out = {"losses": []}
        t = k = 0
        for r in range(rounds):
            h = qsr_h(self.sched, t0 + t)
            losses = []
            for i in range(h):
                lr = jnp.float32(lr_at(self.sched, t0 + t + i))
                kf = jnp.float32(k + i + 1)
                batch = pool[(t + i) % len(pool)]
                for w in range(w_n):
                    bw = jax.device_put(jax.tree.map(lambda x: x[w], batch),
                                        devs[w])
                    loss, ps[w], ms[w], vs[w] = self._step(
                        ps[w], ms[w], vs[w], bw, lr, kf)
                    losses.append(loss)
            per_step = np.asarray(jax.device_get(losses), np.float64)
            out["losses"].append(float(per_step.reshape(h, w_n)
                                       .mean(axis=1).mean()))
            t, k = t + h, k + h
            if "no_sync" not in self.faults:
                mean = self._mean([jax.device_put(p, devs[0]) for p in ps])
                del ps
                ps = [jax.device_put(mean, d) if d != devs[0]
                      else jax.tree.map(jnp.copy, mean) for d in devs]
                del mean
            if r == 0:
                out["grad"] = _by_leaf(params0,
                                       [_one_norms(m) for m in ms])
        out["change"] = _by_leaf(params0, [_change_norms(p, p0)
                                           for p, p0 in zip(ps, p0s)])
        return out
