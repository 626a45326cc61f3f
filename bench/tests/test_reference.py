"""The plain reference against the program at a small size on the CPU: the
models' losses, and whole runs of the harness, whose comparison with the
reference must come out correct."""
import time

import jax
import numpy as np
import pytest

from bench import harness, inputs, manifest
from bench.reference import common as C
from bench.tests.conftest import TINY_CELLS


@pytest.mark.parametrize("name", sorted(TINY_CELLS))
def test_model_loss_matches_the_program(tiny_root, name):
    from repro.configs.base import ModelConfig
    from repro.models import api

    cell = manifest.load_cell(tiny_root, name)
    model, conf = cell.model, cell.conf
    params = jax.jit(lambda k: C.init_params(model.param_shapes(conf), k))(
        C.seed_key(7, C.WEIGHTS))
    pool = inputs.make_pool(model.INPUT, conf, cell.traffic, 1, 7)
    batch = jax.tree.map(lambda x: x[0], pool[0])
    cfg = ModelConfig(**model.program_kwargs(conf))
    want = api.get_module(cfg).loss_fn(cfg, params, batch)
    got = model.loss(conf, params, batch)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    g_prog = jax.grad(lambda p: api.get_module(cfg).loss_fn(cfg, p, batch))(
        params)
    g_ref = jax.grad(lambda p: model.loss(conf, p, batch))(params)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-6)


@pytest.mark.parametrize("name", sorted(TINY_CELLS))
def test_harness_run_is_correct(tiny_root, name):
    out = harness.run_cell(tiny_root, name, 2 ** 40 + 3, 0.2, False,
                           t_process=time.perf_counter(), require_tpu=False)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out["compared"]) == ["loss_gap", "grad_gap", "change_gap"]
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    for c in out["compared"].values():
        assert c["value"] < 1e-4          # float32 on both sides here


def test_memory_peak_counts_the_round_programs_temporaries(tiny_root):
    """The allocator's peak misses a program's temporaries, so the peak the
    run reports is at least the round program's own temporaries."""
    from bench.system import System

    cell = manifest.load_cell(tiny_root, "tiny-decoder.h2")
    sys_ = System(cell)
    state = sys_.init(5)
    state, t, _ = harness.first_rounds(sys_, state, 5)
    peak, mem = harness.peak_bytes(sys_, state,
                                   harness.compiled_round(sys_, state, t))
    prog = mem["program"]
    assert prog["temp"] > 0 and prog["argument"] > 0
    assert peak >= prog["temp"]
    assert peak == max(max(d["peak_bytes_in_use"], d["during_round"])
                       for d in mem["devices"].values())
