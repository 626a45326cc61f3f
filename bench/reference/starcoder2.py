"""Plain reference of the StarCoder2 decoder (arXiv:2402.19173) as the
benchmark's configuration states it: pre-norm blocks of grouped-query
attention with rotary positions and a sliding window, a tanh-GELU MLP,
layer norms, and an LM head tied to the token embedding.

Departures from the published model, each stated in the configuration file:
no biases in the linear layers, layer-norm epsilon 1e-6, no dropout.
Weights are named as the program names them, so one tree feeds both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import common as C

INPUT = "tokens"


def dims(conf: dict) -> dict:
    d, hq = conf["hidden_size"], conf["num_attention_heads"]
    return {"d": d, "f": conf["intermediate_size"], "v": conf["vocab_size"],
            "layers": conf["num_hidden_layers"], "hq": hq,
            "hkv": conf["num_key_value_heads"], "hd": d // hq}


def program_kwargs(conf: dict) -> dict:
    """The configuration in the program's `ModelConfig` vocabulary."""
    dropout = [conf.get(k, 0.0) for k in
               ("attention_dropout", "residual_dropout", "embedding_dropout")]
    if conf["use_bias"] or conf["norm_epsilon"] != 1e-6 or any(dropout):
        raise ValueError("the program's decoder has no linear biases, no "
                         "dropout and a layer-norm epsilon of 1e-6")
    k = dims(conf)
    return dict(name=conf["name"], family="dense", n_layers=k["layers"],
                d_model=k["d"], n_heads=k["hq"], n_kv_heads=k["hkv"],
                d_ff=k["f"], vocab=k["v"], head_dim=k["hd"],
                rope_theta=conf["rope_theta"],
                window=conf["sliding_window"], window_pattern=-1,
                act="gelu", norm="layernorm",
                tie_embeddings=conf["tie_word_embeddings"])


def param_shapes(conf: dict) -> dict:
    k = dims(conf)
    d, f, n = k["d"], k["f"], k["layers"]
    norm = {"scale": (n, d), "bias": (n, d)}
    shapes = {
        "embed": {"tok": (k["v"], d)},
        "layers": {
            "ln1": dict(norm), "ln2": dict(norm),
            "attn": {"wq": (n, d, k["hq"] * k["hd"]),
                     "wk": (n, d, k["hkv"] * k["hd"]),
                     "wv": (n, d, k["hkv"] * k["hd"]),
                     "wo": (n, k["hq"] * k["hd"], d)},
            "mlp": {"wi": (n, d, f), "wo": (n, f, d)},
        },
        "final_norm": {"scale": (d,), "bias": (d,)},
    }
    if not conf["tie_word_embeddings"]:
        shapes["embed"]["head"] = (d, k["v"])
    return shapes


def _rope(x, theta: float):
    """Rotary positions on the two halves of each head: x [S, H, D]."""
    s, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _hidden(conf: dict, params: dict, tokens, num: C.Numerics):
    """Final-norm hidden states of one sequence: tokens [S] -> [S, d]."""
    k = dims(conf)
    eps = conf["norm_epsilon"]
    h = params["embed"]["tok"][tokens].astype(num.dtype)
    s = tokens.shape[0]

    @jax.checkpoint
    def layer(h, p):
        x = C.layer_norm(h, p["ln1"], eps)
        q = num.mm("sd,de->se", x, p["attn"]["wq"]).reshape(s, k["hq"], -1)
        kk = num.mm("sd,de->se", x, p["attn"]["wk"]).reshape(s, k["hkv"], -1)
        v = num.mm("sd,de->se", x, p["attn"]["wv"]).reshape(s, k["hkv"], -1)
        q, kk = _rope(q, conf["rope_theta"]), _rope(kk, conf["rope_theta"])
        o = C.attention(q, kk, v, causal=True, window=conf["sliding_window"],
                        num=num)
        h = h + num.mm("se,ed->sd", o.reshape(s, -1), p["attn"]["wo"])
        x = C.layer_norm(h, p["ln2"], eps)
        u = C.gelu_tanh(num.mm("sd,df->sf", x, p["mlp"]["wi"]))
        return h + num.mm("sf,fd->sd", u, p["mlp"]["wo"]), None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    return C.layer_norm(h, params["final_norm"], eps)


def token_losses(conf: dict, params: dict, tokens, labels,
                 num: C.Numerics = C.FLOAT32, block: int = 1024):
    """Next-token cross entropy of every position of one sequence [S], the
    LM head taken block of positions by block so that the [S, vocab]
    logits are never held whole."""
    h = _hidden(conf, params, tokens, num)
    head = (params["embed"]["tok"].T if conf["tie_word_embeddings"]
            else params["embed"]["head"])
    s = tokens.shape[0]
    blk = min(block, s)
    while s % blk:
        blk -= 1

    @jax.checkpoint
    def one(hb, lb):
        logits = num.mm("sd,dv->sv", hb, head).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold

    out = jax.lax.map(lambda xs: one(*xs),
                      (h.reshape(s // blk, blk, -1),
                       labels.reshape(s // blk, blk)))
    return out.reshape(s)


def loss(conf: dict, params: dict, batch: dict,
         num: C.Numerics = C.FLOAT32):
    """Mean token loss of one worker's batch {tokens, labels} [B, S]."""
    per_seq = [token_losses(conf, params, t, l, num)
               for t, l in zip(batch["tokens"], batch["labels"])]
    return jnp.mean(jnp.stack(per_seq))
