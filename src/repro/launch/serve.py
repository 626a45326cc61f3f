"""Serving driver: batched prefill + decode against any architecture.

Two modes:

  * one-shot batched `generate` (the decode-shape dry-run unit) —
      PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --smoke \
          --batch 4 --prompt-len 32 --gen 16
  * the continuous-batching service loop with hot weight swap
    (`--slots N`): requests flow through launch/batching.py, weights are
    `ServingWeights` flat buckets, and `--watch DIR` subscribes to
    checkpoints a trainer publishes there (launch/weights.py).  `--swap-demo`
    publishes fresh weights mid-decode and `--audit` writes the swap-epoch
    audit trail — per-token checkpoint attribution — as JSON:
      PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --smoke \
          --slots 2 --batch 3 --gen 8 --swap-demo --audit swap_audit.json

CPU-runnable at smoke scale; the same prefill/decode_step programs are what
the dry-run lowers at decode_32k / long_500k shapes.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api, param as pm


def generate(cfg, params, prompts: jax.Array, *, gen_len: int,
             max_len: int | None = None, window_override: int = 0,
             temperature: float = 0.0, seed: int = 0, extra: dict | None = None):
    """prompts [B, P] int32 -> tokens [B, P+gen_len].

    Sampling (temperature > 0) splits one stream per decode step over the
    whole batch: deterministic under a fixed (seed, batch shape), but unlike
    the ContinuousBatcher's per-request streams, a row's samples depend on
    its batch index.
    """
    mod = api.get_module(cfg)
    b, plen = prompts.shape
    # the bidirectional prefix (VLM image tokens) occupies cache positions
    # before the prompt, so it must count toward the default cache length —
    # without it decode positions overrun the cache and JAX's clamping
    # dynamic_update_slice silently corrupts the last rows
    prefix_len = cfg.n_img_tokens if cfg.family == "vlm" else 0
    max_len = max_len or (plen + prefix_len + gen_len)
    cache = mod.init_cache(cfg, b, max_len, dtype=jnp.float32,
                           window_override=window_override)
    kv_len = None
    for k in ("k", "attn_k"):
        if isinstance(cache, dict) and k in cache:
            kv_len = cache[k].shape[2]
    ring = window_override > 0 and kv_len is not None and kv_len < max_len
    if not ring and kv_len is not None and plen + prefix_len + gen_len > kv_len:
        raise ValueError(
            f"prompt ({plen}) + prefix ({prefix_len}) + gen_len ({gen_len}) "
            f"= {plen + prefix_len + gen_len} tokens exceed the KV cache "
            f"length {kv_len}; raise max_len or serve with a ring window")

    extra = extra or {}
    logits, cache = mod.prefill(cfg, params, prompts, cache, **extra)

    decode = jax.jit(
        lambda p, tok, c, pos: mod.decode_step(cfg, p, tok, c, pos,
                                               prefix_len=prefix_len,
                                               ring=ring))
    out = [prompts]
    rng = jax.random.PRNGKey(seed)
    tok = None
    for i in range(gen_len):
        if temperature > 0:
            rng, sub = jax.random.split(rng)
            tok = jax.random.categorical(sub, logits / temperature, axis=-1)
        else:
            tok = jnp.argmax(logits, axis=-1)
        tok = tok.astype(jnp.int32)
        out.append(tok[:, None])
        pos = jnp.asarray(plen + prefix_len + i, jnp.int32)
        logits, cache = decode(params, tok, cache, pos)
    return jnp.concatenate(out, axis=1)


def run_service(cfg, weights, prompts, *, slots: int, max_new: int,
                max_len: int | None = None, temperature: float = 0.0,
                seed: int = 0, subscriber=None, hooks=(),
                max_steps: int = 100_000):
    """Drive the continuous-batching service loop to completion.

    prompts: list of [P] int32 arrays, one request each.  hooks: iterable of
    (step_index, fn(batcher)) one-shot callbacks fired after that many
    decode steps — the CLI's --swap-demo uses one to publish new weights
    mid-decode.  Returns (requests, audit dict)."""
    from repro.launch.batching import ContinuousBatcher, Request
    max_len = max_len or (max(len(p) for p in prompts) + max_new)
    batcher = ContinuousBatcher(cfg, weights, slots=slots, max_len=max_len,
                                temperature=temperature, seed=seed,
                                subscriber=subscriber)
    reqs = [Request(rid=i, prompt=np.asarray(p, np.int32), max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        batcher.submit(r)
    pending = sorted(hooks, key=lambda h: h[0])
    steps = 0
    while steps < max_steps:
        n = batcher.step()
        steps += 1
        while pending and pending[0][0] <= steps:
            pending.pop(0)[1](batcher)
        if n == 0 and not batcher.queue and not pending:
            break
    audit = {
        "arch": cfg.name,
        "family": cfg.family,
        "slots": slots,
        "decode_steps": steps,
        "tokens_emitted": batcher.tokens_emitted,
        "swaps": batcher.swaps,
        "swap_epochs": batcher.weights.audit(),
        "requests": [{"rid": r.rid, "prompt_len": len(r.prompt),
                      "tokens": len(r.out), "epochs": r.epochs}
                     for r in reqs],
    }
    return reqs, audit


def main():
    from repro.launch import multihost
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    multihost.initialize()  # no-op unless REPRO_COORDINATOR is set
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--window", type=int, default=0,
                    help="ring-buffer KV window (long-context serving)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=0,
                    help=">0: continuous-batching service loop with this "
                         "many decode slots (hot-swap capable)")
    ap.add_argument("--watch", default=None,
                    help="poll this dir for published serving checkpoints "
                         "and hot-swap them between decode steps")
    ap.add_argument("--audit", default=None,
                    help="write the swap-epoch audit JSON here")
    ap.add_argument("--swap-demo", action="store_true",
                    help="publish fresh weights mid-decode and hot-swap "
                         "them (exercises the full subscriber path)")
    args = ap.parse_args()

    from repro.configs import registry as R
    cfg = R.get_smoke_config(args.arch) if args.smoke else R.get_config(args.arch)
    mod = api.get_module(cfg)
    params = pm.init_params(mod.param_defs(cfg), jax.random.PRNGKey(0),
                            jnp.float32)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)

    if args.slots > 0:
        _service_main(cfg, mod, params, prompts, args)
        return

    extra = {}
    if cfg.family == "vlm":
        extra["prefix_embeds"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, cfg.n_img_tokens, cfg.d_model))
    if cfg.family == "audio":
        extra["frames"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(3), (args.batch, cfg.enc_seq, cfg.d_model))

    t0 = time.time()
    toks = generate(cfg, params, prompts, gen_len=args.gen,
                    window_override=args.window,
                    temperature=args.temperature, seed=args.seed, extra=extra)
    dt = time.time() - t0
    print(f"generated {args.batch}x{args.gen} tokens in {dt:.2f}s "
          f"({args.batch*args.gen/dt:.1f} tok/s)")
    print("sample:", np.asarray(toks[0])[:args.prompt_len + 8].tolist())


def _service_main(cfg, mod, params, prompts, args):
    """The --slots service-loop entry: hot-swap-capable continuous batching."""
    import tempfile
    from repro.launch import weights as W

    if cfg.family in ("vlm", "audio", "vision"):
        raise SystemExit(f"--slots serves decoder families; {cfg.family} "
                         "prompts need per-request extras the batcher does "
                         "not carry yet")
    weights = W.ServingWeights(cfg, params, step=0, source="init")
    sub = None
    watch = args.watch
    if watch or args.swap_demo:
        watch = watch or tempfile.mkdtemp(prefix="repro-serve-watch-")
        sub = W.WeightSubscriber(watch_dir=watch, like=W.params_like(cfg))
    hooks = []
    if args.swap_demo:
        fresh = pm.init_params(mod.param_defs(cfg), jax.random.PRNGKey(17),
                               jnp.float32)
        # fire after the first requests have cleared slot-local prefill and
        # emitted a few tokens, so the swap lands mid-sequence and the audit
        # shows tokens on both sides of it
        trigger = args.prompt_len + max(2, args.gen // 2)
        hooks.append((trigger, lambda b: W.publish_weights(
            watch, fresh, step=1, extra={"demo": True})))

    t0 = time.time()
    reqs, audit = run_service(
        cfg, weights, [np.asarray(p) for p in prompts], slots=args.slots,
        max_new=args.gen, temperature=args.temperature, seed=args.seed,
        subscriber=sub, hooks=hooks)
    dt = time.time() - t0
    audit["wall_seconds"] = dt
    done = sum(r.done for r in reqs)
    toks = sum(len(r.out) for r in reqs)
    print(f"served {done}/{len(reqs)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s) with {args.slots} slots; "
          f"swaps={audit['swaps']}")
    if args.swap_demo and audit["swaps"] < 1:
        raise SystemExit("--swap-demo: no swap happened (requests finished "
                         "before the publish hook fired)")
    for r in reqs[:2]:
        print(f"  rid={r.rid} tokens={r.out[:8]}... epochs={r.epochs[:8]}...")
    if args.audit:
        with open(args.audit, "w") as f:
            json.dump(audit, f, indent=2)
        print(f"swap-epoch audit -> {args.audit}")


if __name__ == "__main__":
    main()
