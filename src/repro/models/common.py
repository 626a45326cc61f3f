"""Shared neural-net building blocks (pure JAX, functional).

Every block has (a) a ``*_defs`` function producing declarative ParamDefs and
(b) an ``*_apply`` function consuming the materialized params.  Attention and
norms route through ``repro.kernels.ops`` so the Pallas kernels are used on
TPU while CPU falls back to the jnp oracles.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.errors import ShapeError
from repro.kernels import ops as kops
from repro.models.param import ParamDef

def scan_unroll():
    """Full-unroll switch for dry-run cost analysis: XLA's cost_analysis
    counts a while-loop body once, so the roofline pass unrolls every scan
    (REPRO_DRYRUN_UNROLL=1) to get exact FLOP/byte/collective counts."""
    return bool(int(os.environ.get("REPRO_DRYRUN_UNROLL", "0")))


def shard_map_compat(f, mesh, *, in_specs, out_specs, manual_axes=None):
    """`jax.shard_map` over the named *manual* axes (None = every mesh axis).

    Replication checking is disabled: the call sites use
    psum_scatter/all_gather/all_to_all patterns the checker cannot always
    infer through.
    """
    manual = (frozenset(mesh.axis_names) if manual_axes is None
              else frozenset(manual_axes))
    return jax.shard_map(f, mesh=mesh, axis_names=manual,
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def norm_defs(cfg: ModelConfig) -> dict:
    if cfg.norm == "layernorm":
        return {"scale": ParamDef((cfg.d_model,), ("embed",), "ones"),
                "bias": ParamDef((cfg.d_model,), ("embed",), "zeros")}
    return {"scale": ParamDef((cfg.d_model,), ("embed",), "ones")}


def norm_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
        return out.astype(x.dtype)
    return kops.rms_norm(x, p["scale"])


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x [B,S,H,D]; positions [S] or [B,S]."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freq  # [B,S,half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], -1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention (GQA, optional QKV bias / sliding window / prefix-LM / KV cache)
# --------------------------------------------------------------------------

def attn_defs(cfg: ModelConfig) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    defs = {
        "wq": ParamDef((d, hq * hd), ("embed", "heads")),
        "wk": ParamDef((d, hkv * hd), ("embed", "kv")),
        "wv": ParamDef((d, hkv * hd), ("embed", "kv")),
        "wo": ParamDef((hq * hd, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((hq * hd,), ("heads",), "zeros")
        defs["bk"] = ParamDef((hkv * hd,), ("kv",), "zeros")
        defs["bv"] = ParamDef((hkv * hd,), ("kv",), "zeros")
    return defs


def _attn_chunked(q, k, v, *, causal, window, prefix_len, q_offset, q_block=512):
    """Block the query dim so the [Sq,Sk] score tile stays bounded.

    The scan body is checkpointed: the backward recomputes each chunk's
    scores and f32 softmax from (qi, k, v) rather than stacking
    [nblk, B, Hkv, G, q_block, Sk] probabilities and masks for the scan's
    transpose.  One more QK^T and softmax pass per chunk costs less than
    writing and reloading that memory-bound stack.  Without
    differentiation the checkpoint is the plain body.
    """
    b, sq, hq, hd = q.shape
    if sq <= q_block:
        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    prefix_len=prefix_len, q_offset=q_offset)
    while sq % q_block:  # largest divisor of sq at most the target block
        q_block -= 1
    nblk = sq // q_block
    qs = q.reshape(b, nblk, q_block, hq, hd).swapaxes(0, 1)  # [n,b,qb,h,d]

    def body(carry, inp):
        i, qi = inp
        o = kops.flash_attention(qi, k, v, causal=causal, window=window,
                                 prefix_len=prefix_len,
                                 q_offset=q_offset + i * q_block)
        return carry, o

    _, outs = jax.lax.scan(jax.checkpoint(body, prevent_cse=False), 0,
                           (jnp.arange(nblk), qs),
                           unroll=scan_unroll())
    return outs.swapaxes(0, 1).reshape(b, sq, hq, hd)


def attn_apply(cfg: ModelConfig, p: dict, x: jax.Array, *,
               positions: jax.Array, layer_window=0, prefix_len=0,
               cache: dict | None = None, cache_pos=None, ring: bool = False,
               kv_source: jax.Array | None = None, use_rope: bool = True):
    """Returns (out, new_cache).

    cache: {"k": [B,Smax,Hkv,hd], "v": ...} — decode/streaming path.  With
    ring=True the cache is a circular buffer shorter than the stream; keys
    carry their absolute positions for masking.
    kv_source: if given, cross-attention (keys/values from this tensor).
    """
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    src = x if kv_source is None else kv_source
    q = (x @ p["wq"]).reshape(b, s, hq, hd)
    k = (src @ p["wk"]).reshape(b, src.shape[1], hkv, hd)
    v = (src @ p["wv"]).reshape(b, src.shape[1], hkv, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(hq, hd)
        k = k + p["bk"].reshape(hkv, hd)
        v = v + p["bv"].reshape(hkv, hd)
    if use_rope and kv_source is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if kv_source is not None:
        # cross attention: no causal mask, no cache update
        o = _attn_chunked(q, k, v, causal=False, window=0, prefix_len=0,
                          q_offset=0)
    elif cache is not None:
        # decode: write k/v at cache_pos, attend over the whole cache.
        # cache_pos may be per-batch [B] (ragged continuous batching).
        ln = cache["k"].shape[1]
        per_batch = getattr(cache_pos, "ndim", 0) and jnp.ndim(cache_pos) > 0
        if per_batch:
            if ring:
                raise ShapeError("ragged positions + ring cache unsupported")
            dus = jax.vmap(
                lambda c, u, pp: jax.lax.dynamic_update_slice_in_dim(
                    c, u, pp, axis=0))
            ck = dus(cache["k"], k.astype(cache["k"].dtype), cache_pos)
            cv = dus(cache["v"], v.astype(cache["v"].dtype), cache_pos)
            new_cache = {"k": ck, "v": cv}
            o = kops.flash_attention(q, ck, cv, causal=True,
                                     window=layer_window,
                                     prefix_len=prefix_len,
                                     q_offset=cache_pos)
            return o.reshape(b, s, hq * hd) @ p["wo"], new_cache
        if ring:
            write = jnp.mod(cache_pos, ln)
            base = cache_pos - write
            idx = jnp.arange(ln)
            k_positions = jnp.where(idx <= write, base + idx, base - ln + idx)
        else:
            write = cache_pos
            k_positions = None
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), write, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), write, axis=1)
        new_cache = {"k": ck, "v": cv}
        o = kops.flash_attention(q, ck, cv, causal=True, window=layer_window,
                                 prefix_len=prefix_len, q_offset=cache_pos,
                                 k_positions=k_positions)
    else:
        o = _attn_chunked(q, k, v, causal=True, window=layer_window,
                          prefix_len=prefix_len, q_offset=0)
    return o.reshape(b, s, hq * hd) @ p["wo"], new_cache


# --------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# --------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    defs = {"wi": ParamDef((d, f), ("embed", "mlp")),
            "wo": ParamDef((f, d), ("mlp", "embed"))}
    if cfg.act == "swiglu":
        defs["wg"] = ParamDef((d, f), ("embed", "mlp"))
    return defs


def mlp_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.act == "swiglu":
        # fused gate+up projection (Pallas kernel on TPU; jnp oracle on CPU)
        return kops.swiglu(x, p["wg"], p["wi"]) @ p["wo"]
    return jax.nn.gelu(x @ p["wi"]) @ p["wo"]


# --------------------------------------------------------------------------
# Embedding / unembedding
# --------------------------------------------------------------------------

def embed_defs(cfg: ModelConfig) -> dict:
    defs = {"tok": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                            "embed", scale=0.02)}
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    return defs


def embed_apply(cfg: ModelConfig, p: dict, tokens: jax.Array) -> jax.Array:
    h = p["tok"][tokens]
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return h


def unembed_apply(cfg: ModelConfig, p: dict, h: jax.Array) -> jax.Array:
    w = p["tok"].T if cfg.tie_embeddings else p["head"]
    return (h @ w.astype(h.dtype)).astype(jnp.float32)


# --------------------------------------------------------------------------
# Loss
# --------------------------------------------------------------------------

def lm_loss(logits: jax.Array, labels: jax.Array,
            mask: jax.Array | None = None) -> jax.Array:
    """Mean next-token cross-entropy in fp32. logits [..,S,V], labels [..,S]."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def stack_defs(defs, n: int):
    """Prepend a scan 'layers' axis of size n to every ParamDef in a tree."""
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, ("layers",) + d.axes, d.init, d.scale),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
