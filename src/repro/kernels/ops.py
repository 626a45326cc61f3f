"""Jit'd dispatch wrappers for the Pallas kernels.

Backend selection:
  * ``"jnp"``       — pure-jnp reference (default on CPU; identical math to ref.py)
  * ``"pallas"``    — real Pallas lowering (TPU target)
  * ``"interpret"`` — Pallas kernel body interpreted on CPU (used by tests)

Models call these entry points; they never touch pallas_call directly.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from repro.errors import ConfigError
from repro.kernels import ref

_BACKEND = os.environ.get("REPRO_KERNEL_BACKEND", "jnp")


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in ("jnp", "pallas", "interpret"):
        raise ConfigError(f"unknown kernel backend {name!r}")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    if _BACKEND == "jnp":
        return ref.rms_norm(x, scale, eps)
    from repro.kernels import rmsnorm as _k
    return _k.rms_norm(x, scale, eps=eps, interpret=(_BACKEND == "interpret"))


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    prefix_len: int = 0, q_offset=0, scale: float | None = None,
                    k_positions=None):
    """q [B,Sq,H,D], k/v [B,Sk,Hkv,D] (GQA by head broadcast)."""
    import numpy as _np
    ragged = getattr(q_offset, "ndim", 0) and _np.ndim(q_offset) > 0
    if _BACKEND != "jnp" and q.shape[1] == 1 and causal:
        # the serving hot path: single-query decode runs the q-block=1
        # Pallas kernel, which takes window / q_offset (incl. ragged [B]) /
        # ring k_positions as runtime operands — the cases the training
        # kernel's static masks cannot express.
        from repro.kernels import flash_attention as _k
        return _k.flash_decode(q, k, v, causal=causal, window=window,
                               prefix_len=prefix_len, q_offset=q_offset,
                               scale=scale, k_positions=k_positions,
                               interpret=(_BACKEND == "interpret"))
    if _BACKEND == "jnp" or k_positions is not None or ragged:
        # full-sequence ring-buffer / ragged-offset shapes stay on the jnp
        # path: the block kernel takes one scalar query offset and dense
        # key positions.
        return ref.attention(q, k, v, causal=causal, window=window,
                             prefix_len=prefix_len, q_offset=q_offset,
                             scale=scale, k_positions=k_positions)
    from repro.kernels import flash_attention as _k
    return _k.flash_attention(q, k, v, causal=causal, window=window,
                              prefix_len=prefix_len, q_offset=q_offset,
                              scale=scale, interpret=(_BACKEND == "interpret"))


def adamw_update(p, m, v, g, *, lr, beta1, beta2, eps, weight_decay, step):
    """Fused AdamW update for one flat tensor. Returns (new_p, new_m, new_v)."""
    if _BACKEND == "jnp":
        return ref.adamw_update(p, m, v, g, lr=lr, beta1=beta1, beta2=beta2,
                                eps=eps, weight_decay=weight_decay, step=step)
    from repro.kernels import adamw_update as _k
    return _k.adamw_update(p, m, v, g, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                           weight_decay=weight_decay, step=step,
                           interpret=(_BACKEND == "interpret"))


def sync_flat_update(p, anchor, *, scale=None, mu=None, momentum: float = 0.0):
    """Fused flat-bucket sync (delta -> int8 round-trip -> worker mean ->
    Nesterov -> anchor/params) in one pass. Returns (new_p, new_anchor,
    new_mu | None); see kernels/sync_update.py."""
    if _BACKEND == "jnp":
        return ref.sync_flat_update(p, anchor, scale=scale, mu=mu,
                                    momentum=momentum)
    from repro.kernels import sync_update as _k
    return _k.sync_flat_update(p, anchor, scale=scale, mu=mu,
                               momentum=momentum,
                               interpret=(_BACKEND == "interpret"))


def sync_apply_update(step_in, anchor, *, scale=None, mu=None,
                      momentum: float = 0.0):
    """Fused gather-leg apply for one flat bucket: dequantize the worker-mean
    int8 codes (when `scale` is given), outer Nesterov, anchor update — one
    pass. Returns (new_anchor, new_mu | None); see kernels/sync_update.py."""
    if _BACKEND == "jnp":
        return ref.sync_apply_update(step_in, anchor, scale=scale, mu=mu,
                                     momentum=momentum)
    from repro.kernels import sync_update as _k
    return _k.sync_apply_update(step_in, anchor, scale=scale, mu=mu,
                                momentum=momentum,
                                interpret=(_BACKEND == "interpret"))


def ring_combine(q, s, x, k: int):
    """One receive hop of the re-quantizing int8 ring: dequantize incoming
    codes, fold the local chunk into the running mean, emit the next hop's
    amax — fused (kernels/sync_update.py). Returns (acc, amax)."""
    if _BACKEND == "jnp":
        return ref.ring_combine(q, s, x, k)
    from repro.kernels import sync_update as _k
    return _k.ring_combine(q, s, x, k, interpret=(_BACKEND == "interpret"))


def ring_quantize_codes(acc, scale):
    """Send-side half of the per-hop requant pass: int8 codes of a ring
    partial mean under one guarded scalar scale."""
    if _BACKEND == "jnp":
        return ref.ring_quantize_codes(acc, scale)
    from repro.kernels import sync_update as _k
    return _k.ring_quantize(acc, scale, interpret=(_BACKEND == "interpret"))


def swiglu(x, wg, wi):
    """Fused silu(x@wg)*(x@wi) — the MLP hot spot."""
    if _BACKEND == "jnp":
        return ref.swiglu(x, wg, wi)
    from repro.kernels import swiglu as _k
    return _k.swiglu(x, wg, wi, interpret=(_BACKEND == "interpret"))
