"""Launcher set-up: the one-node run, the device line, the host device
count and the compilation cache (``repro.launch.runtime``)."""
import os
import subprocess
import sys

import pytest

from repro.launch import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _train(*args, timeout=600):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--preset", "tiny",
         "--steps", "2", "--batch-per-node", "2", "--seq", "16", *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def test_one_node_local_run_has_no_topology():
    res = _train("--nodes", "1", "--mode", "local")
    assert res.returncode == 0, res.stderr[-3000:]
    lines = res.stdout.splitlines()
    assert lines[0] == "platform cpu device_kind cpu devices 1", lines[0]
    assert "active 0/0" in lines[-1], lines[-1]


@pytest.mark.parametrize("mode", ["matcha", "vanilla", "periodic"])
def test_one_node_gossip_modes_exit_with_a_message(mode):
    res = _train("--nodes", "1", "--mode", mode)
    assert res.returncode != 0
    assert "--nodes 1 needs --mode local" in res.stderr, res.stderr[-2000:]


def test_launcher_forces_virtual_devices_only_on_cpu(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    runtime.force_host_devices(4)
    assert os.environ["XLA_FLAGS"] == (
        "--xla_force_host_platform_device_count=4")
    for platforms in ("", "tpu"):
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        if platforms:
            monkeypatch.setenv("JAX_PLATFORMS", platforms)
        else:
            monkeypatch.delenv("JAX_PLATFORMS")
        runtime.force_host_devices(4)
        assert "XLA_FLAGS" not in os.environ, platforms


def test_compile_cache_dir_is_fixed_unless_set_from_outside(monkeypatch):
    import jax

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        runtime.init_compile_cache()            # CPU: no cache
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        runtime.init_compile_cache()
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
        jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        runtime.init_compile_cache()
        assert jax.config.jax_compilation_cache_dir == "/elsewhere"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
