"""Fused AdamW update — Pallas TPU kernel.

The innermost loop of every local step in Local AdamW (paper Alg. 2 line 12):
p, m, v are streamed through VMEM in lane-dense blocks; all five elementwise
ops (two moment updates, bias correction, weight decay, parameter step) fuse
into one pass, so HBM traffic is the roofline minimum (read p,m,v,g; write
p,m,v) instead of one round-trip per op.

Inputs may be any rank: the kernel views them as [rows, n] with rows the
leading dim (the worker axis W of a flat [W, N] bucket) and blocks the
n dim in multiples of 128 lanes.  The grid covers a ragged last block
without padding (Pallas masks its out-of-bounds writes), so no copy of the
model-sized buffers is made, and p, m, v are updated in place.  Under the
tree layout the optimizer invokes this once per pytree leaf; under the flat
layout (core/flat.py) once per dtype bucket.

The scalars (lr and the two bias corrections 1 - beta**step) are computed
in the wrapper and ride in SMEM.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK = 64 * 1024  # elements per operand block (8 sublanes x 8192 lanes)


def _adamw_kernel(sc_ref, p_ref, m_ref, v_ref, g_ref, po_ref, mo_ref, vo_ref,
                  *, beta1, beta2, eps, weight_decay):
    lr, bc1, bc2 = sc_ref[0], sc_ref[1], sc_ref[2]
    g = g_ref[...].astype(jnp.float32)
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * g * g
    upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    pf = p_ref[...].astype(jnp.float32)
    po_ref[...] = (pf - lr * (upd + weight_decay * pf)).astype(po_ref.dtype)
    mo_ref[...] = m
    vo_ref[...] = v


def lane_block(rows: int, n: int, budget: int = _BLOCK) -> int:
    """Lanes per block of a [rows, n] elementwise kernel: a multiple of 128
    with rows (padded to 8 sublanes) x lanes <= budget, or all of n."""
    lanes = max(128, budget // max(rows, 8) // 128 * 128)
    return n if n <= lanes else lanes


@partial(jax.jit,
         static_argnames=("beta1", "beta2", "eps", "weight_decay", "interpret"))
def adamw_update(p, m, v, g, *, lr, beta1, beta2, eps, weight_decay, step,
                 interpret: bool = False):
    """All tensors same shape; m, v fp32. Returns (new_p, new_m, new_v)."""
    shape = p.shape
    rows = shape[0] if p.ndim >= 2 else 1
    n = p.size // rows
    as2d = lambda x: x.reshape(rows, n)
    step = jnp.asarray(step, jnp.float32)
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         1.0 - beta1 ** step, 1.0 - beta2 ** step])
    blk = lane_block(rows, n)
    spec = pl.BlockSpec((rows, blk), lambda i: (0, i))
    po, mo, vo = pl.pallas_call(
        partial(_adamw_kernel, beta1=beta1, beta2=beta2, eps=eps,
                weight_decay=weight_decay),
        grid=(pl.cdiv(n, blk),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  spec, spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows, n), p.dtype),
                   jax.ShapeDtypeStruct((rows, n), jnp.float32),
                   jax.ShapeDtypeStruct((rows, n), jnp.float32)],
        input_output_aliases={1: 0, 2: 1, 3: 2},
        name="adamw_update",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(scalars, as2d(p), as2d(m), as2d(v), as2d(g))
    return po.reshape(shape), mo.reshape(shape), vo.reshape(shape)
