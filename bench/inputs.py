"""The traffic generator: a pool of per-step training batches made on the
device, in one jitted call, from a traffic file and a seed.

pool[i] is the batch of the i-th step after the traffic's start step, for
all W workers at once: leaves [W, batch_per_worker, ...].  The window
cycles through the pool.  Token ids and labels are uniform over the
vocabulary; images are standard normal in the configuration's image dtype,
their labels uniform over the classes.  Nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import INPUTS, seed_key


def _tokens(conf, traffic, workers):
    shape = (workers, traffic["batch_per_worker"], traffic["seq_len"] + 1)

    def one(key):
        ids = jax.random.randint(key, shape, 0, conf["vocab_size"],
                                 jnp.int32)
        return {"tokens": ids[..., :-1], "labels": ids[..., 1:]}
    return one


def _images(conf, traffic, workers):
    n, b = conf["image_size"], traffic["batch_per_worker"]
    shape = (workers, b, n, n, conf["num_channels"])
    dtype = jnp.dtype(conf["image_dtype"])

    def one(key):
        ki, kl = jax.random.split(key)
        images = jax.random.normal(ki, shape, jnp.float32).astype(dtype)
        labels = jax.random.randint(kl, (workers, b), 0, conf["num_labels"],
                                    jnp.int32)
        return {"images": images, "labels": labels}
    return one


GENERATORS = {"tokens": _tokens, "images": _images}


def tokens_per_example(kind: str, conf: dict, traffic: dict) -> int:
    """Tokens one example trains: its sequence, or its patches."""
    if kind == "tokens":
        return traffic["seq_len"]
    return (conf["image_size"] // conf["patch_size"]) ** 2


def make_pool(kind: str, conf: dict, traffic: dict, workers: int,
              seed: int, sharding=None) -> list[dict]:
    """The pool for `seed`, on the default device; with `sharding` (of the
    leading worker axis over a mesh) each chip makes its own workers'
    batches.  The values do not depend on where they are made."""
    one = GENERATORS[kind](conf, traffic, workers)
    steps = traffic["pool_steps"]
    kw = {} if sharding is None else {"out_shardings": sharding}
    fn = jax.jit(lambda key: [one(jax.random.fold_in(key, i))
                              for i in range(steps)], **kw)
    return fn(seed_key(seed, INPUTS))
