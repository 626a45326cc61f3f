"""The harness on a mesh of chips, rehearsed on four host devices at a
small size (bench/tests/mesh_cell.py, in a process of its own, since the
device count is fixed when JAX starts): the workers' state lies one worker
a device, the compiled round exchanges data between them, the cell runs
correct, and the planted faults and the control fail the limits of the
benchmark's four-chip cell."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests.conftest import ROOT


@pytest.fixture(scope="module")
def mesh_run(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "tests", "mesh_cell.py"),
         str(tmp_path_factory.mktemp("mesh"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_one_worker_a_device(mesh_run):
    assert mesh_run["devices"] == 4
    for rows in mesh_run["workers_on"].values():
        assert sorted(r[0][:2] for r in rows) == [[w, w + 1]
                                                  for w in range(4)]
        assert sorted(r[1] for r in rows) == [0, 1, 2, 3]


def test_the_round_exchanges_between_devices(mesh_run):
    parts = mesh_run["collectives"]
    assert "sync" in parts.values(), parts
    assert set(parts.values()) <= {"sync", "telemetry", "other"}


def test_sync_metrics_read_the_collectives_outside_telemetry(mesh_run):
    parts = mesh_run["collectives"]
    assert "telemetry" in parts.values(), parts
    assert mesh_run["sync_collectives"] == sorted(
        op for op, part in parts.items() if part != "telemetry")


def test_mesh_cell_runs_correct(mesh_run):
    run = mesh_run["run"]
    assert run["correct"] is True, run["compared"]
    assert run["count"] == 4 and run["attempted"] > 0
    for c in run["compared"].values():
        assert c["value"] < 1e-4          # float32 on both sides here


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_sync"])
def test_planted_fault_is_not_correct_on_the_mesh(mesh_run, fault):
    assert mesh_run[fault]["correct"] is False, mesh_run[fault]["compared"]


def test_control_fails_the_mesh_cells_limits(mesh_run):
    for seed, c in mesh_run["control"].items():
        assert c["passes"] is False, (seed, c["found"])
