"""Plain reference of ViT-B/16 (arXiv:2010.11929) as the benchmark's
configuration states it, with the classifier of Beyer et al. 2022
(arXiv:2205.01580), which the QSR paper trains: 16x16 patches projected
linearly, fixed sin-cos positions over the patch index, pre-norm blocks of
full multi-head attention and a tanh-GELU MLP, a final layer norm, the
mean over patches and a linear head.

Departures from the published ViT-B/16, each stated in the configuration
file: no biases in the attention and MLP layers, a pooled head in place of
the class token, fixed one-dimensional sin-cos positions in place of
learned ones.  Weights are named as the program names them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import common as C

INPUT = "images"


def program_kwargs(conf: dict) -> dict:
    """The configuration in the program's `ModelConfig` vocabulary."""
    if (conf["use_bias"] or conf["classifier"] != "gap"
            or conf["layer_norm_eps"] != 1e-6):
        raise ValueError("the program's ViT has no attention/MLP biases, a "
                         "pooled head and a layer-norm epsilon of 1e-6")
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return dict(name=conf["name"], family="vision",
                n_layers=conf["num_hidden_layers"], d_model=d, n_heads=h,
                n_kv_heads=h, d_ff=conf["intermediate_size"], vocab=0,
                act="gelu", norm="layernorm", tie_embeddings=False,
                n_classes=conf["num_labels"])


def param_shapes(conf: dict) -> dict:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    n, p = conf["num_hidden_layers"], conf["patch_size"]
    norm = {"scale": (n, d), "bias": (n, d)}
    return {
        "patch_proj": (p * p * conf["num_channels"], d),
        "patch_bias": (d,),
        "layers": {
            "ln1": dict(norm), "ln2": dict(norm),
            "attn": {"wq": (n, d, d), "wk": (n, d, d), "wv": (n, d, d),
                     "wo": (n, d, d)},
            "mlp": {"wi": (n, d, f), "wo": (n, f, d)},
        },
        "final_norm": {"scale": (d,), "bias": (d,)},
        "head": (d, conf["num_labels"]),
        "head_bias": (conf["num_labels"],),
    }


def _positions(n: int, d: int):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    inv = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32)
                  * (-jnp.log(10000.0) / d))
    pe = jnp.zeros((n, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * inv))
    return pe.at[:, 1::2].set(jnp.cos(pos * inv))


def _patches(conf: dict, images):
    """[B, H, W, C] -> [B, patches, p*p*C], patches in row-major order."""
    b, hh, ww, c = images.shape
    p = conf["patch_size"]
    x = images.reshape(b, hh // p, p, ww // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (hh // p) * (ww // p), p * p * c)


def logits(conf: dict, params: dict, images, num: C.Numerics = C.FLOAT32):
    eps = conf["layer_norm_eps"]
    heads = conf["num_attention_heads"]
    x = _patches(conf, images.astype(jnp.float32))
    b, n, _ = x.shape
    h = num.mm("bnp,pd->bnd", x, params["patch_proj"])
    h = (h + params["patch_bias"]
         + _positions(n, conf["hidden_size"])).astype(num.dtype)
    attend = jax.vmap(lambda q, k, v: C.attention(q, k, v, causal=False,
                                                  window=0, num=num))

    @jax.checkpoint
    def layer(h, p):
        y = C.layer_norm(h, p["ln1"], eps)
        q, k, v = (num.mm("bnd,de->bne", y, p["attn"][w]).reshape(
            b, n, heads, -1) for w in ("wq", "wk", "wv"))
        o = attend(q, k, v).reshape(b, n, -1)
        h = h + num.mm("bne,ed->bnd", o, p["attn"]["wo"])
        y = C.layer_norm(h, p["ln2"], eps)
        u = C.gelu_tanh(num.mm("bnd,df->bnf", y, p["mlp"]["wi"]))
        return h + num.mm("bnf,fd->bnd", u, p["mlp"]["wo"]), None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    h = C.layer_norm(h, params["final_norm"], eps)
    pooled = jnp.mean(h.astype(jnp.float32), axis=1)
    out = num.mm("bd,dk->bk", pooled, params["head"]).astype(jnp.float32)
    return out + params["head_bias"].astype(jnp.float32)


def loss(conf: dict, params: dict, batch: dict,
         num: C.Numerics = C.FLOAT32):
    """Mean cross entropy of one worker's batch {images, labels}."""
    z = logits(conf, params, batch["images"], num)
    gold = jnp.take_along_axis(z, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold)
