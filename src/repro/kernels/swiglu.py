"""Fused SwiGLU activation — Pallas TPU kernel.

Computes silu(x @ wg) * (x @ wi) with one pass over x per output tile:
grid (rows, ff_cols, d_model blocks); each program multiplies a
[block_r, block_d] x tile into [block_d, block_f] tiles of both the gate and
the up projection on the MXU, accumulating in two fp32 VMEM scratch tiles,
and the last d_model block fuses the silu/multiply — the intermediate gate
tensor never round-trips HBM.

Tiling: block_r=256 rows x block_f=512 ff-cols x block_d=512 contraction.
In fp32 that is a 512 KiB x tile, two 1 MiB weight tiles, two 512 KiB
accumulators and a 512 KiB output tile — about 7 MiB with double-buffered
operands, inside v5e's 16 MiB scoped VMEM at any d_model (the contraction
is blocked, so the footprint does not grow with it).

Backward: `swiglu` is a `jax.custom_vjp` whose backward pass is the VJP of
the `ref.swiglu` oracle.  A Pallas backward kernel is future work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref


def _swiglu_kernel(x_ref, wg_ref, wi_ref, o_ref, g_acc, u_acc, *, n_d: int):
    kd = pl.program_id(2)

    @pl.when(kd == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        u_acc[...] = jnp.zeros_like(u_acc)

    x = x_ref[...].astype(jnp.float32)
    g_acc[...] += jnp.dot(x, wg_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)
    u_acc[...] += jnp.dot(x, wi_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)

    @pl.when(kd == n_d - 1)
    def _done():
        g = g_acc[...]
        o_ref[...] = (g * jax.nn.sigmoid(g) * u_acc[...]).astype(o_ref.dtype)


def _tile(n: int, target: int, align: int) -> int:
    """Largest multiple of `align` that divides n and is <= target; n itself
    when none does (a block equal to the full dim is always legal)."""
    for t in range(min(target, n) // align * align, 0, -align):
        if n % t == 0:
            return t
    return n


def _swiglu_forward(x, wg, wi, *, block_r, block_f, block_d, interpret):
    d, f = wg.shape
    lead = x.shape[:-1]
    n = x.size // d
    x2 = x.reshape(n, d)
    br = _tile(n, block_r, 8)
    bf = _tile(f, block_f, 128)
    bd = _tile(d, block_d, 128)
    n_d = d // bd
    out = pl.pallas_call(
        functools.partial(_swiglu_kernel, n_d=n_d),
        grid=(n // br, f // bf, n_d),
        in_specs=[pl.BlockSpec((br, bd), lambda i, j, kd: (i, kd)),
                  pl.BlockSpec((bd, bf), lambda i, j, kd: (kd, j)),
                  pl.BlockSpec((bd, bf), lambda i, j, kd: (kd, j))],
        out_specs=pl.BlockSpec((br, bf), lambda i, j, kd: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, f), x.dtype),
        name="swiglu",
        scratch_shapes=[pltpu.VMEM((br, bf), jnp.float32),
                        pltpu.VMEM((br, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x2, wg, wi)
    return out.reshape(lead + (f,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _swiglu(x, wg, wi, static):
    return _swiglu_forward(x, wg, wi, **dict(static))


def _swiglu_fwd(x, wg, wi, static):
    return _swiglu(x, wg, wi, static), (x, wg, wi)


def _swiglu_bwd(static, res, dout):
    _, vjp = jax.vjp(ref.swiglu, *res)
    return vjp(dout)


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


@functools.partial(jax.jit, static_argnames=("block_r", "block_f", "block_d",
                                             "interpret"))
def swiglu(x: jax.Array, wg: jax.Array, wi: jax.Array, *, block_r: int = 256,
           block_f: int = 512, block_d: int = 512,
           interpret: bool = False) -> jax.Array:
    """x [..., D]; wg, wi [D, F] -> silu(x@wg) * (x@wi), shape [..., F].
    Differentiable: the backward pass is the VJP of `ref.swiglu`."""
    static = (("block_r", block_r), ("block_f", block_f),
              ("block_d", block_d), ("interpret", interpret))
    return _swiglu(x, wg, wi, static)
