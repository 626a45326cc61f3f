"""Fused flat-buffer sync update — Pallas TPU kernel.

One communication-round sync over a dtype bucket of the FlatParamSpace
(core/flat.py): per-worker delta from the anchor, optional int8
quantize/dequantize (per-tensor scales precomputed and spread to elements),
worker mean, optional Nesterov outer momentum, anchor update, and the
broadcast of the new consensus back to every replica — all in ONE pass
through VMEM.  The tree-layout path runs the same math as ~6 separate jnp
ops, each round-tripping the (model-sized) delta through HBM; here HBM
traffic is the roofline minimum: read p, anchor (+ scale, mu), write p,
anchor (+ mu).  The flat-bucket kernels' grids cover a ragged last block
without padding (Pallas masks its out-of-bounds writes), and they update
p, anchor and mu in place, so no copy of a model-sized buffer is made.

The worker-mean all-reduce itself is GSPMD's (the W axis is sharded over
the worker mesh axes); inside the kernel the W axis is the block's leading
dim, so `jnp.mean(axis=0)` stays a local reduction per shard.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BLOCK = 256 * 1024   # elements per (W x blk) tile budget: W*blk <= _BLOCK


def _kernel(refs, *, momentum, quantize, n_in):
    in_refs, out_refs = refs[:n_in], refs[n_in:]
    p_ref, a_ref = in_refs[0], in_refs[1]
    s_ref = in_refs[2] if quantize else None
    mu_ref = in_refs[2 + bool(quantize)] if momentum > 0.0 else None
    po_ref, ao_ref = out_refs[0], out_refs[1]

    af = a_ref[...].astype(jnp.float32)                 # [blk]
    d = p_ref[...].astype(jnp.float32) - af[None]       # [W, blk]
    if quantize:
        # RS-domain rule (core/sync.py): mean the integer codes, dequantize
        # once after — Σq is exact in f32 for any order, which is what keeps
        # this pass bitwise-equal to the sharded layout's reduce_scatter of
        # the same codes.
        s = s_ref[...]
        q = jnp.clip(jnp.round(d / s[None] * 127.0), -127.0, 127.0)
        step = jnp.mean(q, axis=0) * (s / 127.0)
    else:
        step = jnp.mean(d, axis=0)
    if momentum > 0.0:
        mu1 = momentum * mu_ref[...] + step
        step = momentum * mu1 + step                    # Nesterov
        out_refs[2][...] = mu1
    a1 = (af + step).astype(ao_ref.dtype)
    ao_ref[...] = a1
    po_ref[...] = jnp.broadcast_to(a1[None], d.shape).astype(po_ref.dtype)


@partial(jax.jit, static_argnames=("momentum", "interpret"))
def sync_flat_update(p, anchor, *, scale=None, mu=None, momentum: float = 0.0,
                     interpret: bool = False):
    """p [W, N]; anchor [N]; scale [N] or None; mu [N] fp32 iff momentum > 0.
    Returns (new_p, new_anchor, new_mu | None) — see kernels/ref.py oracle."""
    w, n = p.shape
    quantize = scale is not None
    blk = min(n, max(8 * 128, _BLOCK // max(w, 1)))
    args = [p, anchor]
    spec2 = pl.BlockSpec((w, blk), lambda i: (0, i))
    spec1 = pl.BlockSpec((blk,), lambda i: (i,))
    in_specs = [spec2, spec1]
    if quantize:
        args.append(scale)
        in_specs.append(spec1)
    if momentum > 0.0:
        args.append(mu)
        in_specs.append(spec1)
    out_shape = [jax.ShapeDtypeStruct((w, n), p.dtype),
                 jax.ShapeDtypeStruct((n,), anchor.dtype)]
    out_specs = [spec2, spec1]
    aliases = {0: 0, 1: 1}
    if momentum > 0.0:
        out_shape.append(jax.ShapeDtypeStruct((n,), jnp.float32))
        out_specs.append(spec1)
        aliases[len(args) - 1] = 2

    def body(*refs):
        _kernel(refs, momentum=momentum, quantize=quantize, n_in=len(args))

    out = pl.pallas_call(body, grid=(pl.cdiv(n, blk),), in_specs=in_specs,
                         out_specs=out_specs, out_shape=out_shape,
                         input_output_aliases=aliases,
                         name="sync_flat_update",
                         interpret=interpret)(*args)
    return out[0], out[1], (out[2] if momentum > 0.0 else None)


# --------------------------------------------------------------------------
# The gather-leg apply: dequant + outer Nesterov + anchor in one pass
# --------------------------------------------------------------------------

def _apply_kernel(refs, *, momentum, quantize, n_in):
    in_refs, out_refs = refs[:n_in], refs[n_in:]
    q_ref, a_ref = in_refs[0], in_refs[1]
    s_ref = in_refs[2] if quantize else None
    mu_ref = in_refs[2 + bool(quantize)] if momentum > 0.0 else None

    step = q_ref[...]                                   # [blk] f32
    if quantize:
        step = step * (s_ref[...] / 127.0)
    if momentum > 0.0:
        mu1 = momentum * mu_ref[...] + step
        step = momentum * mu1 + step                    # Nesterov
        out_refs[1][...] = mu1
    out_refs[0][...] = (a_ref[...].astype(jnp.float32)
                        + step).astype(out_refs[0].dtype)


@partial(jax.jit, static_argnames=("momentum", "interpret"))
def sync_apply_update(step_in, anchor, *, scale=None, mu=None,
                      momentum: float = 0.0, interpret: bool = False):
    """step_in [N] f32 (the worker-mean codes qmean when `scale` is given,
    else the mean delta); anchor [N]; scale [N] or None; mu [N] fp32 iff
    momentum > 0.  Returns (new_anchor, new_mu | None) — the deferrable
    gather leg of the sync in one VMEM pass; see kernels/ref.py oracle."""
    (n,) = step_in.shape
    quantize = scale is not None
    blk = min(n, _BLOCK)
    args = [step_in, anchor]
    spec1 = pl.BlockSpec((blk,), lambda i: (i,))
    in_specs = [spec1, spec1]
    if quantize:
        args.append(scale)
        in_specs.append(spec1)
    if momentum > 0.0:
        args.append(mu)
        in_specs.append(spec1)
    out_shape = [jax.ShapeDtypeStruct((n,), anchor.dtype)]
    out_specs = [spec1]
    aliases = {1: 0}
    if momentum > 0.0:
        out_shape.append(jax.ShapeDtypeStruct((n,), jnp.float32))
        out_specs.append(spec1)
        aliases[len(args) - 1] = 1

    def body(*refs):
        _apply_kernel(refs, momentum=momentum, quantize=quantize,
                      n_in=len(args))

    out = pl.pallas_call(body, grid=(pl.cdiv(n, blk),), in_specs=in_specs,
                         out_specs=out_specs, out_shape=out_shape,
                         input_output_aliases=aliases,
                         name="sync_apply_update",
                         interpret=interpret)(*args)
    return out[0], (out[1] if momentum > 0.0 else None)


# --------------------------------------------------------------------------
# The per-hop requant pass of the int8 ring (core/sync.py --wire ring-int8)
# --------------------------------------------------------------------------

def _ring_combine_kernel(q_ref, s_ref, x_ref, acc_ref, am_ref, *, k):
    deq = q_ref[...].astype(jnp.float32) * (s_ref[...] / 127.0)
    acc = (jnp.float32(k) * deq + x_ref[...].astype(jnp.float32)) \
        / jnp.float32(k + 1)
    acc_ref[...] = acc
    am_ref[...] = jnp.max(jnp.abs(acc))[None]


@partial(jax.jit, static_argnames=("k", "interpret"))
def ring_combine(q, s, x, k: int, interpret: bool = False):
    """One receive hop of the re-quantizing ring, fused: dequantize the
    incoming int8 codes, fold the local chunk into the running mean, and
    emit the amax the next hop's scale needs — one VMEM pass instead of the
    dequant/mul/add/div/abs/max chain (see kernels/ref.py oracle).

    q [n] int8; s () f32 sender scale; x [n] local chunk.  Returns
    (acc [n] f32, amax () f32)."""
    (n,) = q.shape
    blk = min(n, _BLOCK)
    pad = (-n) % blk
    # pad codes/chunk with zeros: the padded lanes contribute 0 to acc and
    # |0| to the amax fold — both identities
    qq = jnp.pad(q, (0, pad))
    xx = jnp.pad(x, (0, pad))
    grid = (n + pad) // blk
    spec1 = pl.BlockSpec((blk,), lambda i: (i,))
    spec_s = pl.BlockSpec((1,), lambda i: (0,))
    out = pl.pallas_call(
        partial(_ring_combine_kernel, k=k), grid=(grid,),
        in_specs=[spec1, spec_s, spec1],
        out_specs=[spec1, pl.BlockSpec((1,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((n + pad,), jnp.float32),
                   jax.ShapeDtypeStruct((grid,), jnp.float32)],
        interpret=interpret)(qq, jnp.reshape(s, (1,)).astype(jnp.float32), xx)
    return out[0][:n], jnp.max(out[1])


def _ring_quantize_kernel(acc_ref, s_ref, q_ref):
    q_ref[...] = jnp.clip(jnp.round(acc_ref[...] / s_ref[...] * 127.0),
                          -127.0, 127.0).astype(jnp.int8)


@partial(jax.jit, static_argnames=("interpret",))
def ring_quantize(acc, scale, interpret: bool = False):
    """int8 wire codes of a ring partial mean under one guarded scalar
    scale — the send-side half of the per-hop requant pass.  acc [n] f32,
    scale () f32 (already guarded > 0).  Returns q [n] int8."""
    (n,) = acc.shape
    blk = min(n, _BLOCK)
    pad = (-n) % blk
    aa = jnp.pad(acc, (0, pad))
    spec1 = pl.BlockSpec((blk,), lambda i: (i,))
    spec_s = pl.BlockSpec((1,), lambda i: (0,))
    out = pl.pallas_call(
        _ring_quantize_kernel, grid=((n + pad) // blk,),
        in_specs=[spec1, spec_s],
        out_specs=spec1,
        out_shape=jax.ShapeDtypeStruct((n + pad,), jnp.int8),
        interpret=interpret)(aa, jnp.reshape(scale, (1,)).astype(jnp.float32))
    return out[:n]
