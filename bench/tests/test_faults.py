"""What the comparison has to catch, at a small size on the CPU.

Faults planted in the program under the timed path (a step that returns
its state unchanged, half of each worker's batch left out, the mean over
workers left out) must turn a harness run's `correct` false; and the
control, the reference in bfloat16 put in the program's place, must fail
the limits the benchmark's cells use."""
import time

import jax
import pytest

from bench import compare, harness, manifest
from bench.reference import common as C
from bench.reference.train import half_batch
from bench.tests.conftest import TINY_CELLS



def _unchanged(make):
    def wrapped(*a, **k):
        step = make(*a, **k)
        return lambda state, batch, lr: (state, step(state, batch, lr)[1])
    return wrapped


def _half(make):
    def wrapped(*a, **k):
        step = make(*a, **k)
        halve = jax.vmap(half_batch)
        return lambda state, batch, lr: step(state, halve(batch), lr)
    return wrapped


def plant(monkeypatch, fault: str) -> None:
    from repro.core import engine
    from repro.core import local_update as LU
    if fault == "unchanged":
        monkeypatch.setattr(LU, "make_local_step",
                            _unchanged(LU.make_local_step))
    elif fault == "half_batch":
        monkeypatch.setattr(LU, "make_local_step", _half(LU.make_local_step))
    elif fault == "no_sync":
        monkeypatch.setattr(engine, "make_sync",
                            lambda run_cfg, spec=None: lambda state: state)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_sync"])
@pytest.mark.parametrize("name", sorted(TINY_CELLS))
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, name, fault):
    plant(monkeypatch, fault)
    out = harness.run_cell(tiny_root, name, 2 ** 33 + 5, 0.1, False,
                           t_process=time.perf_counter(), require_tpu=False)
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("name", sorted(TINY_CELLS))
def test_control_fails_the_cells_limits(tiny_root, name):
    cell = manifest.load_cell(tiny_root, name)
    limits = cell.limits             # those of the benchmark's cell
    devices = jax.devices()[:1]
    for seed in (1, 2, 3):
        want = harness.reference_readings(
            cell, seed, harness.reference(cell, devices))
        got = harness.reference_readings(
            cell, seed, harness.reference(cell, devices, num=C.BFLOAT16))
        found = compare.gaps(got, want)
        assert not compare.judge(found, limits), found
