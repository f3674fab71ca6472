"""Process-wide JAX setup shared by every entry point: platform pinning,
the persistent compilation cache, and the device identity results carry.

JAX reads ``JAX_PLATFORMS`` once, when it is imported. ``import
sheep_tpu`` imports JAX (the backend registry), so a caller that picks
the platform after that import pins it with :func:`pin_platform`, which
goes through the config and still works as long as no backend has been
initialized. A process started with ``JAX_PLATFORMS`` already set needs
neither.
"""

from __future__ import annotations

import os

# <repo>/.jax_cache: inside the checkout (listed in .gitignore), fixed,
# so every entry point and every process of a run shares one cache
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def pin_platform(platform: str) -> None:
    """Force ``platform`` as the JAX platform after ``jax`` has been
    imported (env var + config, so child processes inherit it too)."""
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


def compilation_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already reads it and nothing else is configured; otherwise the
    cache lives at the one fixed path inside the checkout."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return compilation_cache_dir()


def device_identity() -> dict:
    """``{"platform", "device_kind"}`` of the default device — stamped
    on every JAX backend's result diagnostics so a number always names
    the hardware it came from."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind}
