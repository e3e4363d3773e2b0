"""Where compiled programs are kept between processes.

Entry points (``chip_smoke.py``, the example ``main``s)
call :func:`enable_compile_cache` once, after the platform is chosen and
before their first compile.
The cache directory is part of JAX's cache key, so it must not move
between runs: it is either what ``JAX_COMPILATION_CACHE_DIR`` says (JAX
reads that variable itself; nothing is set in code then) or the fixed
``<checkout>/.jax_cache`` next to the package.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at its fixed place and
    return the directory in use (``None`` on the CPU backend: XLA:CPU
    ties a cached program to the CPU features of the host that compiled
    it and logs an error on every load, and CPU runs are rehearsals)."""
    if jax.default_backend() == "cpu":
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
