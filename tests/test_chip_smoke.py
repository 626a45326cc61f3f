"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its phases
— training runs per kernel backend, kernels against their oracles, the
four-worker mesh checks — pass at tiny sizes (interpret-mode kernels,
simulated host devices)."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from repro.configs import registry
from repro.kernels import ops as kops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(cwd, script, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_result(stdout: str) -> bool:
    return any(line.startswith("{") and '"ok"' in line
               for line in stdout.splitlines())


def test_refuses_without_a_tpu():
    out = _run(ROOT, SCRIPT)
    assert out.returncode != 0
    assert not _printed_result(out.stdout)
    assert "needs a TPU" in out.stderr


def test_refuses_outside_the_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run(str(tmp_path), str(lone))
    assert out.returncode != 0
    assert not _printed_result(out.stdout)


def test_backends_agree_at_tiny_size(monkeypatch):
    monkeypatch.setattr(kops, "_BACKEND", kops.get_backend())
    cs = _load()
    runs = cs.one_chip(registry.get_smoke_config(cs.ARCH), workers=2,
                       b_loc=1, seq=128, backends=("jnp", "interpret"))
    assert [r["H"] for r in runs.values()] == [[2, 2, 2]] * 2
    assert all(r["compiles"] == 1 for r in runs.values())
    # the interpreted kernels compute the reference's function in f32
    assert cs.rel_losses(runs["jnp"]["hist"],
                         runs["interpret"]["hist"]) <= 1e-5


def test_kernel_checks_at_tiny_size():
    cs = _load()
    tiny = {"b": 1, "seq": 256, "hq": 4, "hkv": 2, "hd": 128, "window": 128,
            "rows": 64, "d": 256, "ff": 512, "workers": 2, "n": 70_001}
    res = cs.kernel_checks(tiny, interpret=True)
    assert set(res) == set(cs.KERNEL_RTOL)
    for name, (err, tol) in res.items():
        assert err <= tol, name


def test_four_worker_mesh_checks_on_host_devices():
    """four_chips() on four simulated CPU devices: one worker per device,
    bitwise-equal params after the sync, mesh vs one-device losses."""
    code = (
        "import json, sys\n"
        f"sys.argv = [{SCRIPT!r}]\n"
        "import importlib.util\n"
        f"spec = importlib.util.spec_from_file_location('cs', {SCRIPT!r})\n"
        "cs = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(cs)\n"
        "small = cs.registry.get_smoke_config(cs.ARCH)\n"
        "rec = cs.four_chips(small, small, b_loc=1, seq=64, small_b_loc=2,\n"
        "                    small_seq=32)\n"
        "print(json.dumps(rec))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["post_sync_bitwise_equal"]
    for rows in rec["worker_devices"].values():
        assert sorted(d for ds in rows.values() for d in ds) == [0, 1, 2, 3]
    assert rec["small_mesh_vs_one_device_rel_diff"] <= 1e-5


@pytest.mark.parametrize("layers", [1, 2])
def test_smoke_config_keeps_published_widths(layers):
    cs = _load()
    cfg = cs.smoke_config(layers)
    full = registry.get_config(cs.ARCH)
    assert cfg.n_layers == layers
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff,
            cfg.vocab, cfg.window) == (full.d_model, full.n_heads,
                                       full.n_kv_heads, full.hd, full.d_ff,
                                       full.vocab, full.window)
