"""The command refuses to run, with no result line, without a TPU and
without the program beside the benchmark."""
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import ROOT

ARGS = ["--workload", "starcoder2-3b-L1.h2.1chip", "--seed", "3000000019",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _printed_result(stdout: str) -> bool:
    return any(line.startswith("{") for line in stdout.splitlines())


def test_refuses_without_a_tpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert not _printed_result(out.stdout)
    assert "needs a TPU" in out.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(str(tmp_path))
    assert out.returncode != 0
    assert not _printed_result(out.stdout)


def test_a_cell_refuses_fewer_chips_than_it_asks_for(tmp_path, monkeypatch):
    """bench/run.py checks for one chip before it knows the cell; the
    cell's own check refuses a machine with fewer chips than it asks for."""
    import jax
    import pytest

    from bench import harness
    from bench.tests.conftest import MESH_CELLS, write_benchmark

    class OneTpu:
        platform, device_kind, id = "tpu", "TPU v5 lite", 0

    write_benchmark(str(tmp_path), MESH_CELLS)
    monkeypatch.setattr(jax, "devices", lambda *a: [OneTpu()])
    harness.check_chips(1)
    with pytest.raises(harness.ChipError, match="needs 4 chips"):
        harness.run_cell(str(tmp_path), "tiny-decoder.h2.dp4", 1, 1.0,
                         False, t_process=0.0)
