"""Process-level JAX set-up shared by the entry points.

Two decisions live here so that ``chip_smoke.py``, ``bench.py``, the
``apps/*`` CLIs and the fleet workers make them the same way:

* where JAX's persistent compile cache lives (:func:`enable_compile_cache`);
* which backends a measuring entry point accepts (:func:`require_backend`):
  the TPU, or the CPU only when the caller pinned it with
  ``JAX_PLATFORMS=cpu``. Nothing here falls back to the CPU on its own.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.

    The path is part of the cache key, so the default is fixed: never a
    temporary, pid- or time-based name."""
    return os.environ.get(CACHE_ENV) or os.path.join(_REPO, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at :func:`compile_cache_dir`.

    Call before the process's first compile: JAX settles the cache once.
    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and no
    other directory is set here. Returns the directory in use."""
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


def cpu_pinned() -> bool:
    """Whether the caller asked for the CPU with ``JAX_PLATFORMS=cpu``."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def require_backend() -> str:
    """The backend JAX initialised: ``"tpu"``, or ``"cpu"`` under an
    explicit ``JAX_PLATFORMS=cpu``. Anything else raises ``RuntimeError``
    (a host whose chip JAX could not find must not measure the CPU)."""
    import jax

    platform = jax.default_backend()
    if platform == "tpu" or (platform == "cpu" and cpu_pinned()):
        return platform
    raise RuntimeError(
        f"JAX initialised the {platform!r} backend, not a TPU; set "
        "JAX_PLATFORMS=cpu to run on the CPU on purpose")
