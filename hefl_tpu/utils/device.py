"""Which device a driver runs on, and where compiled programs are kept.

Both decisions are made in the calling process: a driver either pins the
host CPU (its explicit smoke/CPU flag) or requires a TPU, and fails when
it finds neither — a measurement never lands on a device it did not ask
for. The persistent XLA compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, otherwise at a fixed absolute path in
the checkout (the path is part of the cache key, so it must not move with
the working directory).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_COMPILE_CACHE = os.path.join(_CHECKOUT, ".jax_cache")


def select_platform(script: str, cpu: bool) -> str:
    """Pin the host CPU when `cpu` (the driver's smoke/CPU flag), otherwise
    require a TPU. -> the backend name; SystemExit(1) on anything else."""
    import jax

    if cpu:
        jax.config.update("jax_platforms", "cpu")
    want = "cpu" if cpu else "tpu"
    backend = jax.default_backend()
    if backend != want:
        raise SystemExit(
            f"{script}: needs the {want} backend, found {backend!r}"
            + ("" if cpu else
               " — run it on the chip, or set the driver's smoke flag for "
               "the CPU rehearsal")
        )
    return backend


def setup_compile_cache() -> str:
    """Turn on the persistent compile cache. Where
    JAX_COMPILATION_CACHE_DIR is set JAX already resolved it and no
    directory is set here. -> the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return compile_cache_dir()


def compile_cache_dir() -> str | None:
    """The compile-cache directory JAX resolved (environment variable or
    `setup_compile_cache`), None when the cache is off."""
    import jax

    return jax.config.jax_compilation_cache_dir or None
