#!/usr/bin/env python3
"""On-chip smoke test of the QSR training round.

    python3 chip_smoke.py             # one TPU chip
    python3 chip_smoke.py --chips 4   # the four-chip path only

One chip: starcoder2-3b at its published widths, depth cut to LAYERS, trains
through `launch/train.py`'s `train()` (flat param layout, QSR schedule, W
worker replicas on the chip) once under the `jnp` kernel backend and once
under `pallas`; the per-round losses must be finite and agree within
LOSS_RTOL.  Then each of the six Pallas kernels runs once at real widths
against its `kernels/ref.py` oracle.

Four chips: the same configuration on a 4x1 data-parallel mesh
(flat_sharded, one worker per chip): the workers' shards must sit on four
distinct devices and their params must be bitwise equal after the final
sync.  Then the small (`--smoke`) configuration's per-round losses on that
mesh are compared with a mesh-less run of the same four workers on one
device.

Refuses to run (non-zero exit) unless JAX's first device is a TPU.  The
last line of standard output is one JSON object: ok and the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.configs.base import RunConfig  # noqa: E402
from repro.core.engine import RoundEngine  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.launch import hlo_analysis, shapes  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.optim.lr import make_lr_fn  # noqa: E402

ARCH = "starcoder2-3b"
# Depth: the jnp round program at W=2, b_loc=1, seq=4096 needs 17.74 GiB of
# a v5e's 15.75 GiB at two layers and 14.53 GiB at one (memory_analysis of
# the program compiled for a described v5e chip).
LAYERS = 1
WORKERS = 2
B_LOC = 1
SEQ = 4096
STEPS = 6            # three communication rounds of H = 2
# jnp vs pallas per-round loss agreement, relative.  On the CPU (f32 XLA
# dots, interpreted kernels) the two backends agree to ~1e-7; on the TPU
# XLA's default precision feeds f32 matmul inputs to the MXU as bf16
# (relative rounding 2**-9) in the jnp path, and the per-token errors
# average over the W x seq tokens of a round's loss.  2e-3 is four such
# roundings, and over 10**4 times the CPU disagreement.
LOSS_RTOL = 2e-3
# the small configuration on a mesh vs on one device: same programs up to
# the partitioning, so only reduction order may differ
MESH_RTOL = 1e-3


def run_config(steps: int = STEPS) -> RunConfig:
    """QSR, H = max(2, floor((alpha/lr)^2)) = 2 for every round here."""
    return RunConfig(schedule="qsr", optimizer="adamw", total_steps=steps,
                     peak_lr=1e-3, alpha=1e-4, h_base=2, warmup_steps=1,
                     remat=True)


def smoke_config(layers: int = LAYERS):
    """starcoder2-3b's published widths, depth cut to `layers`."""
    return shapes.with_depth(registry.get_config(ARCH), layers)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def train_rounds(cfg, backend: str, *, workers: int, b_loc: int, seq: int,
                 steps: int = STEPS, layout: str = "flat", mesh=None):
    """One QSR training run through train() under kernel `backend`.
    Returns (engine, final state, history rows (t_end, h, loss, lr))."""
    kops.set_backend(backend)
    run_cfg = run_config(steps)
    eng = RoundEngine(cfg, run_cfg, workers=workers, b_loc=b_loc, seq=seq,
                      layout=layout, mesh=mesh, policy="dp")
    state, hist = train(cfg, run_cfg, workers=workers, b_loc=b_loc, seq=seq,
                        layout=layout, eng=eng)
    losses = [loss for _, _, loss, _ in hist]
    check(len(hist) >= 3, f"{len(hist)} rounds, want >= 3")
    check(any(h >= 2 for _, h, _, _ in hist), "no round with H >= 2")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    return eng, state, hist


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def rel_losses(a, b) -> float:
    return max(abs(x - y) / abs(x) for (_, _, x, _), (_, _, y, _)
               in zip(a, b))


def one_chip(cfg, *, workers: int, b_loc: int, seq: int,
             backends=("jnp", "pallas")) -> dict:
    """One training run of `cfg` per kernel backend; returns their
    records, with the Pallas kernels of each non-jnp round program."""
    out = {}
    for backend in backends:
        log(f"backend={backend} arch={cfg.name} L={cfg.n_layers} "
            f"W={workers} b_loc={b_loc} seq={seq} layout=flat "
            f"schedule=qsr")
        t0 = time.perf_counter()
        eng, state, hist = train_rounds(cfg, backend, workers=workers,
                                        b_loc=b_loc, seq=seq)
        rec = {"seconds": time.perf_counter() - t0,
               "losses": [loss for _, _, loss, _ in hist],
               "H": [h for _, h, _, _ in hist],
               "compiles": eng.compile_stats()["compiles"],
               "peak_bytes_in_use": peak_bytes(), "hist": hist}
        if backend != "jnp":
            t_first, h_first = eng.h_trace[0]
            rec["kernels"] = hlo_analysis.pallas_kernels(eng.compiled_round(
                state, t_first, h_first, make_lr_fn(eng.run_cfg)).as_text())
        del state, eng
        log(f"backend={backend} " + json.dumps(
            {k: v for k, v in rec.items() if k != "hist"}))
        out[backend] = rec
    return out


# --------------------------------------------------------------------------
# Kernels against their oracles
# --------------------------------------------------------------------------

# (name, rel tol of max|got - want| over max|want|): matmul kernels are
# bounded by the MXU's bf16 passes, elementwise ones by f32 rounding
KERNEL_RTOL = {"flash_attention": 2e-2, "flash_decode": 2e-2, "swiglu": 2e-2,
               "rms_norm": 1e-4, "adamw_update": 1e-4,
               "sync_flat_update": 1e-5}

# starcoder2-3b attention widths; gemma3-4b MLP/norm widths; the elementwise
# kernels on [W, d_model * d_ff] — starcoder2-3b's largest tensor per worker
REAL_SIZES = {"b": 1, "seq": SEQ, "hq": 24, "hkv": 2, "hd": 128,
              "window": 4096, "rows": 2048, "d": 2560, "ff": 10240,
              "workers": WORKERS, "n": 3072 * 12288}


def kernel_checks(sizes: dict = REAL_SIZES, *, interpret: bool = False,
                  seed: int = 0) -> dict:
    """Each kernel once at `sizes` against its ref.py oracle (computed at
    matmul precision "highest").  Returns {name: (rel err, tol)}."""
    from repro.kernels.adamw_update import adamw_update
    from repro.kernels.flash_attention import flash_attention, flash_decode
    from repro.kernels.rmsnorm import rms_norm
    from repro.kernels.swiglu import swiglu
    from repro.kernels.sync_update import sync_flat_update

    s = sizes
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    normal = lambda *shape: jax.random.normal(next(keys), shape, jnp.float32)
    q = normal(s["b"], s["seq"], s["hq"], s["hd"])
    k = normal(s["b"], s["seq"], s["hkv"], s["hd"])
    v = normal(s["b"], s["seq"], s["hkv"], s["hd"])
    x = normal(s["rows"], s["d"])
    wg = normal(s["d"], s["ff"]) / s["d"] ** 0.5
    wi = normal(s["d"], s["ff"]) / s["d"] ** 0.5
    scale = 1.0 + 0.1 * normal(s["d"])
    p = normal(s["workers"], s["n"])
    m = 0.1 * normal(s["workers"], s["n"])
    v2 = jnp.abs(0.1 * normal(s["workers"], s["n"]))
    g = normal(s["workers"], s["n"])
    anchor = normal(s["n"])
    win, last = s["window"], s["seq"] - 1
    adam = dict(lr=1e-3, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1,
                step=3.0)
    # inputs are arguments, never closed over: a captured array would be
    # compiled into the program as a constant
    cases = {
        "flash_attention": (
            lambda q, k, v: flash_attention(q, k, v, window=win,
                                            interpret=interpret),
            lambda q, k, v: ref.attention(q, k, v, window=win), (q, k, v)),
        "flash_decode": (
            lambda q, k, v: flash_decode(q, k, v, window=win, q_offset=last,
                                         interpret=interpret),
            lambda q, k, v: ref.attention(q, k, v, window=win,
                                          q_offset=last),
            (q[:, -1:], k, v)),
        "swiglu": (partial(swiglu, interpret=interpret), ref.swiglu,
                   (x, wg, wi)),
        "rms_norm": (partial(rms_norm, interpret=interpret), ref.rms_norm,
                     (x, scale)),
        "adamw_update": (partial(adamw_update, interpret=interpret, **adam),
                         partial(ref.adamw_update, **adam), (p, m, v2, g)),
        "sync_flat_update": (
            lambda p, a: sync_flat_update(p, a, interpret=interpret)[:2],
            lambda p, a: ref.sync_flat_update(p, a)[:2], (p, anchor)),
    }
    out = {}
    for name, (kernel, oracle, args) in cases.items():
        got = jax.tree.leaves(jax.jit(kernel)(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.tree.leaves(jax.jit(oracle)(*args))
        err = max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                  for a, b in zip(got, want))
        out[name] = (err, KERNEL_RTOL[name])
        log(f"kernel {name}: rel err {err:.3e} (tol {KERNEL_RTOL[name]:g})")
    return out


# --------------------------------------------------------------------------
# Four chips
# --------------------------------------------------------------------------

def worker_devices(bufs: dict) -> dict:
    """{bucket: {worker index: {device ids holding that worker's row}}}."""
    out = {}
    for b, x in bufs.items():
        rows: dict[int, set] = {}
        for sh in x.addressable_shards:
            for w in range(*sh.index[0].indices(x.shape[0])):
                rows.setdefault(w, set()).add(sh.device.id)
        out[b] = rows
    return out


@jax.jit
def _rows_bitwise_equal(x):
    bits = {2: jnp.uint16, 4: jnp.uint32}[jnp.dtype(x.dtype).itemsize]
    u = jax.lax.bitcast_convert_type(x, bits)
    return jnp.all(u == u[:1])


def workers_bitwise_equal(bufs: dict) -> bool:
    return all(bool(_rows_bitwise_equal(x)) for x in bufs.values())


def four_chips(cfg, small, *, b_loc: int, seq: int, small_b_loc: int,
               small_seq: int) -> dict:
    """`cfg` on a 4x1 dp mesh (one worker per device): placement and the
    post-sync bitwise check; then `small` on that mesh vs mesh-less."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 1), ("data", "model"))
    log(f"mesh 4x1 dp arch={cfg.name} L={cfg.n_layers} W=4 b_loc={b_loc} "
        f"seq={seq} layout=flat_sharded")
    t0 = time.perf_counter()
    eng, state, hist = train_rounds(cfg, "jnp", workers=4, b_loc=b_loc,
                                    seq=seq, layout="flat_sharded",
                                    mesh=mesh)
    seconds = time.perf_counter() - t0
    placement = worker_devices(state["params"])
    for b, rows in placement.items():
        devs = [next(iter(d)) for d in rows.values()]
        check(len(rows) == 4 and all(len(d) == 1 for d in rows.values())
              and len(set(devs)) == 4,
              f"bucket {b}: workers on devices {rows}")
    bitwise = workers_bitwise_equal(state["params"])
    check(bitwise, "post-sync worker params differ")
    rec = {"seconds": seconds,
           "losses": [loss for _, _, loss, _ in hist],
           "compiles": eng.compile_stats()["compiles"],
           "peak_bytes_in_use": peak_bytes(),
           "worker_devices": {b: {w: sorted(d) for w, d in rows.items()}
                              for b, rows in placement.items()},
           "post_sync_bitwise_equal": bitwise}
    del state, eng
    log("mesh " + json.dumps(rec))

    _, _, h_mesh = train_rounds(small, "jnp", workers=4, b_loc=small_b_loc,
                                seq=small_seq, layout="flat_sharded",
                                mesh=mesh)
    _, _, h_one = train_rounds(small, "jnp", workers=4, b_loc=small_b_loc,
                               seq=small_seq, layout="flat_sharded")
    diff = rel_losses(h_mesh, h_one)
    log(f"{small.name}: mesh losses {[x for _, _, x, _ in h_mesh]} "
        f"one-device losses {[x for _, _, x, _ in h_one]} max rel diff "
        f"{diff:.3e} (tol {MESH_RTOL:g})")
    check(diff <= MESH_RTOL, f"mesh vs one-device losses differ by {diff}")
    rec["small_mesh_vs_one_device_rel_diff"] = diff
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh path")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device {dev.device_kind} x{len(devices)}; compile cache "
        f"{enable_compile_cache()}")

    if args.chips == 4:
        four_chips(smoke_config(), registry.get_smoke_config(ARCH),
                   b_loc=B_LOC, seq=SEQ, small_b_loc=2, small_seq=64)
    else:
        runs = one_chip(smoke_config(), workers=WORKERS, b_loc=B_LOC,
                        seq=SEQ)
        diff = rel_losses(runs["jnp"]["hist"], runs["pallas"]["hist"])
        log(f"jnp vs pallas per-round losses: max rel diff {diff:.3e} "
            f"(tol {LOSS_RTOL:g})")
        check(diff <= LOSS_RTOL, f"backends disagree by {diff}")
        kernels = runs["pallas"]["kernels"]
        log(f"pallas round program: {len(kernels)} tpu_custom_call "
            f"{sorted(set(kernels))}")
        check("flash_attention" in kernels,
              "no attention kernel in the pallas round program")
        t0 = time.perf_counter()
        for name, (err, tol) in kernel_checks().items():
            check(err <= tol, f"kernel {name}: rel err {err} > {tol}")
        log(f"kernel checks took {time.perf_counter() - t0:.1f}s")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
