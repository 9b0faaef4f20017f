"""Process set-up shared by the launchers and ``chip_smoke.py``.

``force_host_devices`` runs before jax is imported; the other two right
after. The host device count is forced only where the run is held to
the CPU (``JAX_PLATFORMS=cpu``): elsewhere a backend that fails to start
must not be replaced by a CPU that happens to have as many devices as
the run asked for.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]          # the checkout
# <checkout>/.jax_cache: fixed, so that a later process finds what an
# earlier one compiled (the path is part of the cache key)
CACHE_DIR = ROOT / ".jax_cache"


def force_host_devices(count: int) -> None:
    """Give the CPU backend ``count`` devices when the run is held to
    the CPU and ``XLA_FLAGS`` is not already set."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={count}"
        )


def init_compile_cache() -> None:
    """Persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` where
    it is set (jax reads it itself), else :data:`CACHE_DIR` on an
    accelerator. CPU runs (tests, rehearsals; many processes at once)
    keep no cache of their own.

    The key includes the ops' metadata: without it a step whose named
    scopes changed gets the executable compiled before the change, and
    its profiler trace carries the old scope names. Source paths in the
    metadata are taken relative to the checkout, so that the same code
    in another checkout still finds its executable."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(f"{ROOT}{os.sep}"))
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    if jax.default_backend() != "cpu":
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def device_line() -> str:
    """``platform <p> device_kind <k> devices <n>`` as jax reports them."""
    import jax

    devs = jax.devices()
    return (f"platform {devs[0].platform} device_kind {devs[0].device_kind} "
            f"devices {len(devs)}")
