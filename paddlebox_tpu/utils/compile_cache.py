"""Where XLA's persistent compile cache lives.

A cold start compiles every program the run uses, and on the chip that is
the larger part of a short run.  JAX can keep compiled programs on disk,
keyed by program, compile options, device kind — and the directory's own
path, so a cache that moves never hits.  Process entry points (benchmark/run.py,
chip_smoke.py, ``python -m paddlebox_tpu.serve``, the examples; launch.py
hands it to its children through the environment) call
:func:`enable_compile_cache` before their first compile.  The package never
does at import, and nothing does under pytest: tier-1 pins compile counts,
and every compile it counts should be a real one.
"""

from __future__ import annotations

import os
from typing import Optional

_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> Optional[str]:
    """The cache directory this process should use: ``$JAX_COMPILATION_
    CACHE_DIR`` when set, else ``<checkout>/.jax_cache`` — the same path
    for every process of every run; None under pytest."""
    if "PYTEST_CURRENT_TEST" in os.environ:
        return None
    if os.environ.get(_ENV):
        return os.environ[_ENV]
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(root, ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on for this process and return
    its directory (None under pytest).  With ``$JAX_COMPILATION_CACHE_DIR``
    set, JAX has already taken the directory from it and none is set here."""
    path = compile_cache_dir()
    if path is None:
        return None
    import jax

    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every program, not only the slow ones: a run is hundreds of
    # small eager programs around a few large steps
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path

