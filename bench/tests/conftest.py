import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIGS = {
    "tiny-decoder": {
        "architecture": "starcoder2", "hidden_size": 64,
        "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 1, "vocab_size": 256,
        "sliding_window": 16, "rope_theta": 10000.0, "norm_epsilon": 1e-6,
        "use_bias": False, "tie_word_embeddings": True},
    "tiny-vit": {
        "architecture": "vit", "hidden_size": 32, "intermediate_size": 64,
        "num_attention_heads": 4, "num_hidden_layers": 2, "image_size": 32,
        "patch_size": 16, "num_channels": 3, "num_labels": 10,
        "layer_norm_eps": 1e-6, "use_bias": False, "classifier": "gap",
        "image_dtype": "bfloat16"},
}
OPT = {"name": "adamw", "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
       "weight_decay": 0.1}
TINY_TRAFFIC = {
    "tiny-h2": {"workers": 2, "layout": "flat", "mesh": None, "remat": True,
                "batch_per_worker": 1, "seq_len": 32, "pool_steps": 4,
                "start_step": 10, "compare_steps": 3, "optimizer": OPT,
                "schedule": {"rule": "qsr", "h_base": 2, "alpha": 0.0015,
                             "lr_schedule": "cosine", "peak_lr": 0.001,
                             "end_lr": 1e-5, "warmup_steps": 10,
                             "total_steps": 10000000}},
    "tiny-img": {"workers": 4, "layout": "flat", "mesh": None,
                 "remat": False, "batch_per_worker": 4, "pool_steps": 8, "start_step": 10,
                 "compare_steps": 3, "optimizer": OPT,
                 "schedule": {"rule": "qsr", "h_base": 4, "alpha": 0.0175,
                              "lr_schedule": "cosine", "peak_lr": 0.008,
                              "end_lr": 1e-6, "warmup_steps": 10,
                              "total_steps": 10000000}},
}
# the four-worker decoder traffic on a 4x1 data-parallel mesh, one worker a
# device: needs four devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)
TINY_TRAFFIC["tiny-h2-dp4"] = dict(
    TINY_TRAFFIC["tiny-h2"], workers=4, layout="flat_sharded",
    mesh={"shape": [4, 1], "axes": ["data", "model"], "policy": "dp"})


def _limits(cell: str) -> dict:
    with open(os.path.join(ROOT, "bench", "cells", cell + ".json")) as f:
        return json.load(f)


# each small cell holds the limits of the benchmark's cell of its model
TINY_CELLS = {
    "tiny-decoder.h2": ("tiny-decoder", "tiny-h2",
                        _limits("starcoder2-3b-L1.h2.1chip")),
    "tiny-vit.h4": ("tiny-vit", "tiny-img", _limits("vit-b16.h4.1chip")),
}
MESH_CELLS = {
    "tiny-decoder.h2.dp4": ("tiny-decoder", "tiny-h2-dp4",
                            _limits("starcoder2-3b-L1.h2.4chip")),
}


def write_benchmark(root, cells=TINY_CELLS) -> None:
    """A checkout-like directory holding BENCHMARK.json and the data files
    of `cells` (name -> (config, traffic, cell file))."""
    base = os.path.join(root, "bench")
    for sub in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(base, sub), exist_ok=True)
    man = {"command": ["python3", "bench/run.py"], "paths": ["bench"],
           "run_seconds": 1, "configs": [], "workloads": [],
           "end_to_end": [
               {"name": "tokens_per_s", "unit": "tokens/s",
                "better": "higher", "bound": 0.03, "source": "host_clock"},
               {"name": "setup_s", "unit": "s", "better": "lower",
                "bound": 0.25, "source": "host_clock"}],
           "per_layer": [
               {"name": "compiles_in_window", "unit": "count",
                "better": "lower", "source": "program_counter",
                "layer": "engine", "moves": "tokens_per_s"}]}
    seen = set()
    for name, (conf, traffic, cell) in cells.items():
        if conf not in seen:
            seen.add(conf)
            man["configs"].append({"name": conf, "source": "test",
                                   "file": f"bench/configs/{conf}.json",
                                   "reduced": [], "why": "test"})
            with open(os.path.join(base, "configs", conf + ".json"),
                      "w") as f:
                json.dump(TINY_CONFIGS[conf], f)
        with open(os.path.join(base, "traffic", traffic + ".json"),
                  "w") as f:
            json.dump(TINY_TRAFFIC[traffic], f)
        with open(os.path.join(base, "cells", name + ".json"), "w") as f:
            json.dump(cell, f)
        mesh = TINY_TRAFFIC[traffic]["mesh"]
        chips = mesh["shape"][0] * mesh["shape"][1] if mesh else 1
        man["workloads"].append({"name": name, "config": conf,
                                 "traffic": traffic, "chips": chips,
                                 "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)


@pytest.fixture
def tiny_root(tmp_path):
    write_benchmark(str(tmp_path))
    return str(tmp_path)
