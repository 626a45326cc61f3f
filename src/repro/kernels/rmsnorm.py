"""Fused RMSNorm forward — Pallas TPU kernel.

Tiling: rows are blocked along the flattened batch/sequence dim; the full
feature dim stays resident in VMEM (d_model <= 8192 -> 8192*4B*block_rows
well under the ~16 MiB VMEM budget at block_rows=256).  Feature dim is
lane-aligned (multiples of 128) for all assigned configs.

Backward: `rms_norm` is a `jax.custom_vjp` whose backward pass is the VJP
of the `ref.rms_norm` oracle.  A Pallas backward kernel is future work.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_norm_forward(x, scale, eps, block_rows, interpret):
    orig_shape = x.shape
    d = x.shape[-1]
    n = x.size // d
    x2 = x.reshape(n, d)
    br = min(block_rows, n)
    while n % br:
        br //= 2
    out = pl.pallas_call(
        partial(_rmsnorm_kernel, eps=eps),
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        name="rms_norm",
        interpret=interpret,
    )(x2, scale)
    return out.reshape(orig_shape)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rms_norm(x, scale, eps, block_rows, interpret):
    return _rms_norm_forward(x, scale, eps, block_rows, interpret)


def _rms_norm_fwd(x, scale, eps, block_rows, interpret):
    return _rms_norm(x, scale, eps, block_rows, interpret), (x, scale)


def _rms_norm_bwd(eps, block_rows, interpret, res, dout):
    _, vjp = jax.vjp(lambda x, s: ref.rms_norm(x, s, eps), *res)
    return vjp(dout)


_rms_norm.defvjp(_rms_norm_fwd, _rms_norm_bwd)


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rms_norm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
             block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x [..., D]; scale [D].  Differentiable: the backward pass is the VJP
    of `ref.rms_norm`."""
    return _rms_norm(x, scale, eps, block_rows, interpret)
