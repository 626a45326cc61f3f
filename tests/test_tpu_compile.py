"""The Pallas kernels against the TPU v5e compiler, and their gradients.

Compile half: each kernel is compiled ahead of time for a *described* v5e
chip at the widths the on-chip smoke test runs (starcoder2-3b attention and
flat buckets, gemma3-4b MLP and norm), which catches what interpret mode
cannot — block shapes the TPU does not tile, operations Mosaic cannot
legalize, VMEM overflows.  Nothing runs.  The topology is described inside
a fixture (never at import), so a worker that cannot load the TPU compiler
skips these tests instead of changing what the others collect.

Gradient half (CPU): the custom_vjp of flash_attention, rms_norm and swiglu
under the interpret backend matches jax.grad of the ref.py oracle.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.adamw_update import adamw_update
from repro.kernels.flash_attention import flash_attention, flash_decode
from repro.kernels.rmsnorm import rms_norm
from repro.kernels.swiglu import swiglu
from repro.kernels.sync_update import sync_flat_update
from repro.launch import hlo_analysis


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# starcoder2-3b: 24 query heads / 2 KV heads of 128, window 4096, seq 4096;
# the flat f32 bucket of its two-layer cut (343M params) for W=2 workers
B, S, HQ, HKV, HD, WIN = 1, 4096, 24, 2, 128, 4096
N = 343_000_001
# gemma3-4b: d_model 2560, d_ff 10240, 2048 rows
ROWS, D, FF = 2048, 2560, 10240
ADAM = dict(beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1)


def _cases():
    i32, f32 = jnp.int32, jnp.float32
    return {
        "flash_attention": (
            lambda q, k, v, w: flash_attention(q, k, v, window=w),
            [((B, S, HQ, HD), f32), ((B, S, HKV, HD), f32),
             ((B, S, HKV, HD), f32), ((), i32)]),
        "flash_decode": (
            lambda q, k, v, w, pos: flash_decode(q, k, v, window=w,
                                                 q_offset=pos),
            [((B, 1, HQ, HD), f32), ((B, S, HKV, HD), f32),
             ((B, S, HKV, HD), f32), ((), i32), ((B,), i32)]),
        "adamw_update": (
            lambda p, m, v, g, lr, step: adamw_update(
                p, m, v, g, lr=lr, step=step, **ADAM),
            [((2, N), f32)] * 4 + [((), f32), ((), f32)]),
        "swiglu": (swiglu, [((ROWS, D), f32), ((D, FF), f32),
                            ((D, FF), f32)]),
        "rms_norm": (rms_norm, [((ROWS, D), f32), ((D,), f32)]),
        "sync_flat_update": (lambda p, a: sync_flat_update(p, a),
                             [((2, N), f32), ((N,), f32)]),
    }


@pytest.mark.parametrize("name", list(_cases()))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert hlo_analysis.pallas_kernels(compiled.as_text()) == [name]


def test_flash_attention_backward_compiles_for_v5e(one_chip):
    """The custom_vjp backward (chunked VJP of ref.attention) at real
    widths: its per-block score tiles fit the chip."""
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, f32, sharding=one_chip)
            for s in ((B, S, HQ, HD), (B, S, HKV, HD), (B, S, HKV, HD))]
    loss = lambda q, k, v: jnp.sum(flash_attention(q, k, v, window=WIN))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * 2**30


# ------------------------------------------------------ gradients (CPU) --

def _assert_grads_close(got, want, tol=1e-4):
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=tol,
                                   atol=tol)


@pytest.mark.parametrize("window,q_offset,hkv", [(0, 0, 4), (24, 0, 2),
                                                 (16, 40, 1)])
def test_flash_attention_grad_matches_oracle(window, q_offset, hkv):
    """window and q_offset ride in traced, as the model scan passes them."""
    ks = jax.random.split(jax.random.PRNGKey(window + q_offset), 4)
    q = jax.random.normal(ks[0], (2, 64, 4, 32))
    k = jax.random.normal(ks[1], (2, 64 + q_offset, hkv, 32))
    v = jax.random.normal(ks[2], (2, 64 + q_offset, hkv, 32))
    ct = jax.random.normal(ks[3], q.shape)

    def loss(attn):
        return lambda q, k, v, w, off: jnp.sum(
            attn(q, k, v, window=w, q_offset=off) * ct)

    kernel = lambda q, k, v, **kw: flash_attention(
        q, k, v, block_q=32, block_k=32, interpret=True, **kw)
    args = (q, k, v, jnp.int32(window), jnp.int32(q_offset))
    got = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(*args)
    want = jax.grad(loss(ref.attention), argnums=(0, 1, 2))(*args)
    _assert_grads_close(got, want)


def test_rms_norm_grad_matches_oracle():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16, 256))
    sc = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (256,))
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    loss = lambda f: lambda x, s: jnp.sum(f(x, s) * ct)
    got = jax.grad(loss(lambda x, s: rms_norm(x, s, interpret=True)),
                   argnums=(0, 1))(x, sc)
    want = jax.grad(loss(ref.rms_norm), argnums=(0, 1))(x, sc)
    _assert_grads_close(got, want)


def test_swiglu_grad_matches_oracle():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (2, 32, 256))
    wg = jax.random.normal(ks[1], (256, 384)) / 16.0
    wi = jax.random.normal(ks[2], (256, 384)) / 16.0
    ct = jax.random.normal(ks[3], (2, 32, 384))
    loss = lambda f: lambda x, wg, wi: jnp.sum(f(x, wg, wi) * ct)
    kernel = lambda x, wg, wi: swiglu(x, wg, wi, block_r=32, block_f=128,
                                      block_d=128, interpret=True)
    got = jax.grad(loss(kernel), argnums=(0, 1, 2))(x, wg, wi)
    want = jax.grad(loss(ref.swiglu), argnums=(0, 1, 2))(x, wg, wi)
    _assert_grads_close(got, want)


def test_training_attention_runs_the_kernel_under_interpret(monkeypatch):
    """A layer's window arrives as a scan xs tracer; under a Pallas backend
    the training attention must still run the block kernel."""
    from repro.kernels import flash_attention as fa
    calls = []
    orig = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention",
                        lambda *a, **kw: calls.append(kw) or orig(*a, **kw))
    monkeypatch.setattr(kops, "_BACKEND", "interpret")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))

    def layers(windows):
        def body(c, w):
            return c, kops.flash_attention(q, k, v, window=w)
        return jax.lax.scan(body, 0, windows)[1]

    windows = jnp.asarray([0, 16], jnp.int32)
    got = jax.jit(layers)(windows)
    assert calls, "traced-window attention did not reach the kernel"
    for i, w in enumerate((0, 16)):
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(ref.attention(q, k, v, window=w)),
            rtol=1e-5, atol=1e-5)
