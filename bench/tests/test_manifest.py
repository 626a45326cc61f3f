"""BENCHMARK.json and the files it names: the contract's limits on names,
units and keys, and every name resolving to a file."""
import json
import os
import re

import pytest

from bench import manifest
from bench.tests.conftest import ROOT, write_benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
WIDTH = re.compile(r"(_dim|_rank)$|^(hidden|intermediate|\w*latent\w*|"
                   r"\w*state\w*|\w*projection\w*)_size$|head_dim|"
                   r"expansion|experts_per_tok")


@pytest.fixture(scope="module")
def man():
    return manifest.load_manifest(ROOT)


def test_top_level_keys(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    for p in man["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(man["command"]) <= 32
    for word in man["command"]:
        assert not word.startswith("/") and ".." not in word


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_names_and_keys(man, section):
    entries = man[section]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") \
            else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for text in ("why", "layer", "source"):
            if text in e:
                assert 1 <= len(e[text]) <= 200 and "\n" not in e[text]
                assert "\t" not in e[text]


def test_configs_files_and_cuts(man):
    files = [c["file"] for c in man["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in man["workloads"]}
    for c in man["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(man["paths"][0] + "/")
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTH.search(k), k
            # every changed key states its published value in the file
            assert k in conf["published"] and conf[k] != conf["published"][k]


def test_cells_resolve_by_name(man):
    cells = {w["name"] for w in man["workloads"]}
    pairs = {(w["config"], w["traffic"]) for w in man["workloads"]}
    assert len(pairs) == len(man["workloads"])
    four = [w for w in man["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(man["workloads"]) // 2)
    e2e = {m["name"] for m in man["end_to_end"]}
    assert "setup_s" in e2e
    assert all(m["bound"] <= 0.25 for m in man["end_to_end"])
    for m in man["end_to_end"] + man["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        manifest.metric_reader(m["name"])       # its reader exists
    for w in man["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = manifest.load_cell(ROOT, w["name"])
        assert cell.per_layer and len(cell.end_to_end) >= 2
        assert cell.limits.keys() == {"loss_gap", "grad_gap",
                                               "change_gap"}
        assert cell.model.param_shapes(cell.conf)


def test_per_layer_workloads_report_what_they_move(man):
    for m in man["per_layer"]:
        for w in m.get("workloads", []):
            cell = manifest.load_cell(ROOT, w)
            assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_a_cell_added_as_files_only_is_found(tiny_root):
    cells = {"tiny-decoder.h2": ("tiny-decoder", "tiny-h2",
                                 {"limits": {}})}
    write_benchmark(tiny_root, cells)
    cell = manifest.load_cell(tiny_root, "tiny-decoder.h2")
    assert cell.conf["name"] == "tiny-decoder"
    assert cell.traffic["seq_len"] == 32 and cell.traffic["workers"] == 2
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["compiles_in_window"]
    with pytest.raises(KeyError):
        manifest.load_cell(tiny_root, "no-such-cell")
