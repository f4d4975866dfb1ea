"""Where JAX's persistent compilation cache lives — the one place that says.

Every entry point that compiles (``main()``, ``benchmark/run.py``,
``chip_smoke.py``'s children, ``__graft_entry__``, the ``tools/`` profilers)
calls :func:`configure_compile_cache` before its first compile. The
directory is part of the cache's lookup, so it must not move between
processes: it is either where the operator put it
(``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself — nothing is set in
code then) or ``<checkout>/.jax_cache`` (listed in ``.gitignore``). Never a
temp name, a pid or a timestamp.
"""
from __future__ import annotations

import os
from typing import Optional

#: the directory holding the package, i.e. the checkout root
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def configure_compile_cache() -> Optional[str]:
    """Place the persistent compile cache; returns the directory in use, or
    None when this process keeps none. Call before the first compile; it
    reads flags only and never initializes a backend.

    A process PINNED to the CPU platform (``JAX_PLATFORMS=cpu``, the test
    suite, ``launch.py`` children, the virtual-mesh rehearsals) places no
    cache of its own: what it compiles is small, and XLA:CPU's loader logs
    a machine-mismatch error for every entry it reads back, on the machine
    that wrote it. An operator who sets ``JAX_COMPILATION_CACHE_DIR`` gets
    a cache there on any platform — that is JAX's doing, not this code's."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    if (jax.config.jax_platforms or "").split(",")[0].strip() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
