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
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    paths = jax.config.jax_hlo_source_file_canonicalization_regex
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
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          keyed)
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          paths)


SCOPED_COMPILE = """
import sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from repro.launch import runtime
import scoped

runtime.init_compile_cache()
jax.config.update("jax_compilation_cache_dir", sys.argv[2])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
print(scoped.compiled(sys.argv[3]))
"""

SCOPED_MODULE = """
import jax, jax.numpy as jnp


def compiled(name):
    def f(x):
        with jax.named_scope(name):
            return jnp.sin(x) @ x

    return jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
"""


def test_compile_cache_keeps_apart_steps_whose_scopes_differ(tmp_path):
    """The same program under another named scope is not served the
    executable cached for the first (its ops carry their own scope, as
    the profiler trace needs), while the same code from another
    checkout is."""
    import shutil

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("PYTHONPATH", None)
    cache = tmp_path / "cache"
    checkouts = [tmp_path / "a", tmp_path / "b"]
    for root in checkouts:
        shutil.copytree(os.path.join(REPO, "src", "repro"),
                        root / "src" / "repro",
                        ignore=shutil.ignore_patterns("__pycache__"))
        (root / "scoped.py").write_text(SCOPED_MODULE)

    def compile_in(root, name):
        res = subprocess.run(
            [sys.executable, "-c", SCOPED_COMPILE, str(root), str(cache),
             name],
            capture_output=True, text=True, timeout=300, env=env)
        assert res.returncode == 0, res.stderr[-3000:]
        assert f"/{name}/sin" in res.stdout
        return set(os.listdir(cache))

    first = compile_in(checkouts[0], "before")
    assert compile_in(checkouts[1], "before") == first     # a cache hit
    assert compile_in(checkouts[1], "after") > first       # compiled anew
