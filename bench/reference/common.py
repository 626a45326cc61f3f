"""What both reference models share: seeded keys, the weight
initialization, plain layer norm, the tanh GELU, and matmuls whose
precision the caller chooses.

Nothing here imports the program under test.  The benchmark makes the
initial weights and the inputs with these functions; the program receives
them as its inputs, and the reference makes them again from the seed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# stream ids folded into the seed's key: weights and inputs never share draws
WEIGHTS, INPUTS = 0, 1


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key for `stream` from a seed of up to 64 bits (PRNGKey alone
    keeps only the low 32)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must lie in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


def leaf_paths(tree) -> list[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def shape_paths(shapes) -> list[str]:
    """Leaf paths of a tree of shape tuples, as `leaf_paths` gives them for
    the weights made from it."""
    flat = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]
    return [jax.tree_util.keystr(p) for p, _ in flat]


def _init_leaf(name: str, key, shape, dtype):
    """Norm scales start at one and biases at zero; the token embedding is
    normal with std 0.02; every matrix is normal with std 1/sqrt(fan-in),
    fan-in being its second-to-last dimension."""
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name.endswith("bias"):
        return jnp.zeros(shape, dtype)
    std = 0.02 if name == "tok" else 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_params(shapes: dict, key, dtype=jnp.float32):
    """Weights for a tree of shapes, one key per leaf in flattening order."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = [_init_leaf(path[-1].key, jax.random.fold_in(key, i), shape,
                         dtype)
              for i, (path, shape) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


class Numerics:
    """How a reference computes: the dtype its weights and activations are
    kept in and the precision of its matmuls, which accumulate in float32.
    FLOAT32 is the reference; BFLOAT16 is its control."""

    def __init__(self, dtype, precision):
        self.dtype, self.precision = dtype, precision

    def mm(self, spec: str, a, b):
        out = jnp.einsum(spec, a.astype(self.dtype), b.astype(self.dtype),
                         precision=self.precision,
                         preferred_element_type=jnp.float32)
        return out.astype(self.dtype)


FLOAT32 = Numerics(jnp.float32, jax.lax.Precision.HIGHEST)
BFLOAT16 = Numerics(jnp.bfloat16, jax.lax.Precision.DEFAULT)


def layer_norm(x, p, eps: float):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    out = (xf - mu) / jnp.sqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def gelu_tanh(x):
    xf = x.astype(jnp.float32)
    c = math.sqrt(2.0 / math.pi)
    out = 0.5 * xf * (1.0 + jnp.tanh(c * (xf + 0.044715 * xf ** 3)))
    return out.astype(x.dtype)


def attention(q, k, v, *, causal: bool, window: int, num: Numerics,
              q_block: int = 512):
    """Softmax attention of one example, computed block of queries by block
    of queries so that no full score matrix is kept for the backward pass.
    q [S, Hq, D]; k, v [S, Hkv, D]; query head j reads key/value head
    j // (Hq // Hkv).  With `causal`, query i sees keys (i - window, i]
    (window 0: all keys up to i)."""
    s, hq, d = q.shape
    g = hq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scale = 1.0 / math.sqrt(d)
    qb = min(q_block, s)
    while s % qb:
        qb -= 1
    keys = jnp.arange(s)

    @jax.checkpoint
    def block(qi, start):
        sc = num.mm("qhd,khd->hqk", qi, k).astype(jnp.float32) * scale
        if causal:
            pos = start + jnp.arange(qb)[:, None]
            ok = keys[None, :] <= pos
            if window > 0:
                ok &= keys[None, :] > pos - window
            sc = jnp.where(ok[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return num.mm("hqk,khd->qhd", p, v)

    blocks = q.reshape(s // qb, qb, hq, d)
    starts = jnp.arange(s // qb) * qb
    out = jax.lax.map(lambda xs: block(*xs), (blocks, starts))
    return out.reshape(s, hq, d)
