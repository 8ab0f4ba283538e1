"""``chip_smoke.py``'s phases at a tiny D on the CPU.

The chip run itself needs a TPU; these tests keep its control flow and
its comparisons honest on every change. The kernel paths the runtimes
take on a TPU are steered on here (``native_kernels``), and the kernels
run in the Pallas interpreter, which the platform picks on the CPU.
"""
import importlib
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def kernel_paths(monkeypatch):
    for name in ("repro.kernels.fleet_ingest", "repro.runtime.runtime"):
        monkeypatch.setattr(
            importlib.import_module(name), "native_kernels", lambda: True
        )


def test_phase_served_matches_reference(smoke, kernel_paths, tmp_path):
    res = smoke.phase_served(
        n_devices=8, n_requests=24, clients=4, window=4, workdir=tmp_path
    )
    assert smoke.check_phase(res, native=False) == [], res
    assert res["acks"] == {"ok": 24} and res["merges"] >= 1


def test_phase_paged_matches_reference(smoke, kernel_paths):
    res = smoke.phase_paged(n_devices=64, cohort=16)
    assert smoke.check_phase(res, native=False) == [], res
    assert (res["ticks"], res["merges"]) == (4, 1)


def test_phase_sharded_matches_one_chip(smoke, kernel_paths):
    res = smoke.phase_sharded(jax.devices()[:1], n_devices=8, window=4)
    assert smoke.check_phase(res, native=False) == [], res
    assert len(res["shards"]) == 1


def test_check_phase_flags_failures(smoke):
    res = {"phase": "x", "acks": {"ok": 3, "failed": 1}, "requests": 4,
           "ticks": 3, "merges": 0, "beta_rel_err": 2e-3,
           "ingest_custom_calls": 0}
    bad = smoke.check_phase(res)
    assert len(bad) == 5, bad


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_chip_smoke_refuses_the_cpu():
    out = _run(ROOT / "chip_smoke.py", ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU found" in out.stderr


def test_chip_smoke_needs_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""


def test_serve_fleet_exits_nonzero_on_failed_acks(monkeypatch, capsys):
    from repro.launch import cache, serve_fleet
    from repro.runtime import FleetRuntime

    monkeypatch.setattr(cache, "enable_compile_cache", lambda: "")

    def broken_tick(self, batch, **kw):
        raise RuntimeError("injected tick failure")

    monkeypatch.setattr(FleetRuntime, "tick", broken_tick)
    rc = serve_fleet.main([
        "--devices", "4", "--requests", "8", "--clients", "2",
    ])
    assert rc == 1
    assert '"failed": ' in capsys.readouterr().out


_CACHE_PROBE = """
import json, sys
import jax, jax.numpy as jnp
from repro.launch.cache import CHECKOUT_CACHE_DIR, enable_compile_cache
path = enable_compile_cache()
jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)).block_until_ready()
print(json.dumps({"path": path, "checkout": str(CHECKOUT_CACHE_DIR),
                  "config": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_placement(tmp_path, env_dir):
    import json

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.splitlines()[-1])
    if env_dir:
        # the variable's directory, and no other, receives the entries
        assert got["path"] == got["config"] == str(tmp_path / "cache")
        assert any((tmp_path / "cache").iterdir())
    else:
        assert got["path"] == got["config"] == got["checkout"]
        assert got["checkout"] == str(ROOT / ".jax_cache")
