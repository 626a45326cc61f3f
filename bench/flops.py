"""Model FLOPs of one training example, from the configuration's sizes.

Counted as the forward and backward passes require them: 6 FLOPs per
matmul weight per token it is applied to (2 forward, 4 backward), and for
attention 12 FLOPs per query, key and head dimension the mask lets through
(QK^T and PV, each 2 forward and 4 backward).  A causal query attends to
itself and the keys before it, within the window.  Recomputation under
remat does not count, nor do norms, activations, the softmax and the
optimizer.
"""
from __future__ import annotations


def causal_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a causal mask with a sliding window lets through:
    query i sees min(i + 1, window) keys (window 0: i + 1)."""
    w = window if window > 0 else seq
    full = min(seq, w)
    # queries 0..full-1 see i+1 keys; the rest see w
    return full * (full + 1) // 2 + (seq - full) * w


def starcoder2(conf: dict, traffic: dict) -> dict:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, n = d // hq, conf["num_hidden_layers"]
    seq = traffic["seq_len"]
    per_layer = d * hq * hd * 2 + d * hkv * hd * 2 + 2 * d * f
    head = d * conf["vocab_size"]
    matmul = n * per_layer + head
    attn = 12 * n * hq * hd * causal_pairs(seq, conf["sliding_window"])
    return {"matmul_params": matmul, "tokens": seq,
            "flops": 6 * matmul * seq + attn}


def vit(conf: dict, traffic: dict) -> dict:
    d, f, n = (conf["hidden_size"], conf["intermediate_size"],
               conf["num_hidden_layers"])
    p, c = conf["patch_size"], conf["num_channels"]
    tokens = (conf["image_size"] // p) ** 2
    body = p * p * c * d + n * (4 * d * d + 2 * d * f)
    head = d * conf["num_labels"]          # once per image, on the mean
    attn = 12 * n * d * tokens * tokens
    return {"matmul_params": body + head, "tokens": tokens,
            "flops": 6 * body * tokens + 6 * head + attn}


COUNTERS = {"starcoder2": starcoder2, "vit": vit}


def per_example(conf: dict, traffic: dict) -> dict:
    """{"flops", "tokens", "matmul_params"} of one training example.  An
    architecture not counted here is counted by the `flops` function of its
    reference module, bench/reference/<architecture>.py."""
    arch = conf["architecture"]
    if arch in COUNTERS:
        return COUNTERS[arch](conf, traffic)
    import importlib
    return importlib.import_module(f"bench.reference.{arch}").flops(
        conf, traffic)
