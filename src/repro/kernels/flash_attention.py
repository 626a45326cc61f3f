"""Flash attention (causal GQA + sliding window + prefix-LM) — Pallas TPU.

Tiling (MXU/VMEM-aware):
  The wrapper moves heads ahead of the sequence ([B,S,H,D] -> [B,H,S,D]) so
  every block's last two dims are a (rows, head_dim) tile the TPU tiles
  natively.  grid = (batch, q_heads, n_q_blocks, n_k_blocks); the innermost
  grid dim walks K blocks while fp32 accumulators (running max / denominator
  / output) persist in VMEM scratch — the classic online-softmax flash
  schedule.  Default blocks 128x128: q,k,v tiles are 128x128 (64 KiB each in
  fp32) and the fp32 score tile is 64 KiB — inside the VMEM budget, and
  every matmul dim is a multiple of the 128-lane MXU width.

GQA is expressed in the BlockSpec index maps: the kv index map divides the
query-head grid coordinate by the group size, so no head replication ever
materializes in HBM.

The query offset and the sliding window are RUNTIME int32 scalars (scalar
prefetch, in SMEM): the model scan feeds per-layer windows as scan xs and
the query-chunked attention feeds traced offsets, and neither specializes
the kernel.  `causal` and `prefix_len` stay static.

Backward: `flash_attention` is a `jax.custom_vjp` whose backward pass is the
VJP of the `ref.attention` oracle, chunked over query blocks so the score
matrix is never materialized whole.  A Pallas backward kernel is future
work.

`flash_decode` is the single-query serving variant (q-block = 1): one query
per sequence against the paged/ring KV cache, grid (batch, kv_heads,
k_blocks), the whole GQA group's [g, d] query tile resident per program.
Its window and per-batch query positions are runtime scalars too, and ring
caches pass absolute key positions as an int32 operand.  Dispatch:
ops.flash_attention routes every sq==1 causal call here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.errors import ShapeError
from repro.kernels import ref

NEG_INF = -1e30

_NT = (((1,), (1,)), ((), ()))   # dot_general dims for a @ b.T


def _online_softmax_step(s, v, acc_ref, m_ref, l_ref):
    """Fold one masked score tile s [r, bk] and its V tile [bk, d] into the
    running (acc, max, denominator) accumulators."""
    m_prev = m_ref[...]                                   # [r, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _init_accumulators(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _normalized(acc_ref, l_ref):
    l = l_ref[...]
    safe = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows -> 0 output
    return acc_ref[...] / safe


def _flash_kernel(s_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, prefix_len: int, block_q: int,
                  block_k: int, n_k: int, kv_len: int):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        _init_accumulators(acc_ref, m_ref, l_ref)

    q_offset, window = s_ref[0], s_ref[1]
    q = q_ref[0, 0].astype(jnp.float32)                 # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)                 # [bk, d]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * scale

    q_idx = (i * block_q + q_offset
             + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
    k_idx = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = k_idx < kv_len
    if causal:
        ok &= k_idx <= q_idx
    ok &= (window <= 0) | (k_idx > q_idx - window)
    if prefix_len > 0:
        ok |= k_idx < prefix_len
    _online_softmax_step(jnp.where(ok, s, NEG_INF), v, acc_ref, m_ref, l_ref)

    @pl.when(j == n_k - 1)
    def _done():
        o_ref[0, 0] = _normalized(acc_ref, l_ref).astype(o_ref.dtype)


def _heads_major(x, seq_pad: int):
    """[B,S,H,D] -> [B,H,S+seq_pad,D]."""
    x = x.transpose(0, 2, 1, 3)
    if seq_pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, seq_pad), (0, 0)))
    return x


def _flash_forward(q, k, v, scalars, *, causal, prefix_len, scale, block_q,
                   block_k, interpret):
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    n_q = (sq + pad_q) // bq
    n_k = (sk + pad_k) // bk

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, prefix_len=prefix_len,
        block_q=bq, block_k=bk, n_k=n_k, kv_len=sk)
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j, s: (b_, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda b_, h, i, j, s: (b_, h // g, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hq, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq + pad_q, d), q.dtype),
        name="flash_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(scalars, _heads_major(q, pad_q), _heads_major(k, pad_k),
      _heads_major(v, pad_k))
    return out[:, :, :sq].transpose(0, 2, 1, 3)


def _ref_vjp(q, k, v, do, scalars, *, causal, prefix_len, scale,
             block_q=512):
    """(dq, dk, dv) of `ref.attention`, one query block at a time: each
    block's scores are [.., block_q, Sk], and dk/dv accumulate in fp32."""
    b, sq, hq, d = q.shape
    qb = min(block_q, sq)
    while sq % qb:
        qb -= 1
    n = sq // qb
    q_offset, window = scalars[0], scalars[1]

    def body(carry, xs):
        dk, dv = carry
        i, qi, doi = xs

        def attend(qi_, k_, v_):
            return ref.attention(qi_, k_, v_, causal=causal, window=window,
                                 prefix_len=prefix_len,
                                 q_offset=q_offset + i * qb, scale=scale)

        _, vjp = jax.vjp(attend, qi, k, v)
        dqi, dki, dvi = vjp(doi)
        return (dk + dki.astype(jnp.float32),
                dv + dvi.astype(jnp.float32)), dqi

    blocks = lambda x: x.reshape(b, n, qb, hq, d).swapaxes(0, 1)
    zeros = lambda x: jnp.zeros(x.shape, jnp.float32)
    (dk, dv), dqs = jax.lax.scan(body, (zeros(k), zeros(v)),
                                 (jnp.arange(n), blocks(q), blocks(do)))
    dq = dqs.swapaxes(0, 1).reshape(q.shape)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash(q, k, v, scalars, static):
    return _flash_forward(q, k, v, scalars, **dict(static))


def _flash_fwd(q, k, v, scalars, static):
    return _flash(q, k, v, scalars, static), (q, k, v, scalars)


def _flash_bwd(static, res, do):
    q, k, v, scalars = res
    st = dict(static)
    dq, dk, dv = _ref_vjp(q, k, v, do, scalars, causal=st["causal"],
                          prefix_len=st["prefix_len"], scale=st["scale"])
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "prefix_len", "scale", "block_q",
                              "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, prefix_len=0,
                    q_offset=0, scale=None, block_q=128, block_k=128,
                    interpret=False):
    """q [B,Sq,Hq,D]; k,v [B,Sk,Hkv,D] -> [B,Sq,Hq,D].

    `window` and `q_offset` are scalars and may be traced.  Differentiable:
    the backward pass is the VJP of `ref.attention` (see module doc)."""
    _, _, hq, d = q.shape
    hkv = k.shape[2]
    if hq % hkv != 0:
        raise ShapeError(f"GQA needs Hq % Hkv == 0, got ({hq}, {hkv})")
    scale = float(scale) if scale is not None else d ** -0.5
    scalars = jnp.stack([jnp.asarray(q_offset, jnp.int32).reshape(()),
                         jnp.asarray(window, jnp.int32).reshape(())])
    static = (("causal", causal), ("prefix_len", prefix_len),
              ("scale", scale), ("block_q", block_q), ("block_k", block_k),
              ("interpret", interpret))
    return _flash(q, k, v, scalars, static)


# --------------------------------------------------------------------------
# Single-query decode kernel (serving hot path)
# --------------------------------------------------------------------------

def _decode_kernel(s_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, causal: bool,
                   prefix_len: int, n_k: int):
    """One (batch row, kv head) pair's GQA group against one K block.

    The online-softmax accumulators are [g, 1]-shaped (g = query heads per
    kv head): the whole group shares the K/V tiles, so GQA costs one K/V
    read per GROUP instead of per query head.  Mask semantics mirror
    ref._mask exactly; `k_idx` comes from the kpos operand (arange for a
    dense cache, absolute stream positions for a ring buffer, -1 marking
    padding/empty), and the window (s_ref[0]) and this row's absolute query
    position (s_ref[1 + b]) are runtime scalars."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_accumulators(acc_ref, m_ref, l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # [g, d]
    k = k_ref[0, 0].astype(jnp.float32)                 # [bk, d]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * scale

    win = s_ref[0]
    qpos = s_ref[1 + pl.program_id(0)]
    k_idx = jnp.broadcast_to(kpos_ref[...], s.shape)
    valid = k_idx >= 0                                  # -1 = pad / empty
    ok = valid
    if causal:
        ok &= k_idx <= qpos
    ok &= (win <= 0) | (k_idx > qpos - win)
    if prefix_len > 0:
        ok |= valid & (k_idx < prefix_len)              # bidirectional prefix
    _online_softmax_step(jnp.where(ok, s, NEG_INF), v, acc_ref, m_ref, l_ref)

    @pl.when(j == n_k - 1)
    def _done():
        o_ref[0, 0] = _normalized(acc_ref, l_ref).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "prefix_len", "scale", "block_k",
                              "interpret"))
def flash_decode(q, k, v, *, causal=True, window=0, prefix_len=0, q_offset=0,
                 scale=None, k_positions=None, block_k=128, interpret=False):
    """Single-query decode: q [B,1,Hq,D] against a KV cache k/v [B,Sk,Hkv,D].

    `window` (scalar) and `q_offset` (scalar or per-batch [B] — ragged
    continuous batching) may be TRACED; they ride in as int32 scalars.
    `k_positions [Sk]` serves the ring-buffer cache: absolute stream
    position per cache row, -1 for empty.  Returns [B,1,Hq,D].
    """
    b, sq, hq, d = q.shape
    if sq != 1:
        raise ShapeError(f"flash_decode is the single-query kernel, Sq={sq}")
    _, sk, hkv, _ = k.shape
    if hq % hkv != 0:
        raise ShapeError(f"GQA needs Hq % Hkv == 0, got ({hq}, {hkv})")
    g = hq // hkv
    scale = float(scale) if scale is not None else d ** -0.5

    qoff = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1),
                            (b,))
    scalars = jnp.concatenate(
        [jnp.asarray(window, jnp.int32).reshape(1), qoff])
    kpos = (jnp.arange(sk, dtype=jnp.int32) if k_positions is None
            else jnp.asarray(k_positions, jnp.int32))

    bk = min(block_k, sk)
    pad_k = (-sk) % bk
    if pad_k:
        kpos = jnp.pad(kpos, (0, pad_k), constant_values=-1)
    n_k = (sk + pad_k) // bk
    kpos = kpos.reshape(1, sk + pad_k)
    qg = q.reshape(b, hkv, g, d)     # head h = kv*g + gi, same grouping as ref

    kernel = functools.partial(_decode_kernel, scale=scale, causal=causal,
                               prefix_len=prefix_len, n_k=n_k)
    group_spec = pl.BlockSpec((1, 1, g, d), lambda b_, h, j, s: (b_, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d), lambda b_, h, j, s: (b_, h, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, n_k),
        in_specs=[pl.BlockSpec((1, bk), lambda b_, h, j, s: (0, j)),
                  group_spec, kv_spec, kv_spec],
        out_specs=group_spec,
        scratch_shapes=[pltpu.VMEM((g, d), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32)])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        name="flash_decode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(scalars, kpos, qg, _heads_major(k, pad_k), _heads_major(v, pad_k))
    return out.reshape(b, 1, hq, d)
