"""Where the persistent XLA compile cache lives.

One rule, applied by every entry point that compiles a large surface
(``bench.py``, the ``serve``/``train``/``generate`` CLI commands and
``chip_smoke.py``): if ``JAX_COMPILATION_CACHE_DIR`` is set, jax already
uses it and this module sets nothing; otherwise the cache is
``<checkout>/.jax_cache``. The path is part of every cache key's
lookup, so it never carries a temp name, pid or timestamp. The test
suite stays uncached (``tests/conftest.py`` says why).
"""

from __future__ import annotations

import os
from pathlib import Path

_CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point jax at the compile cache and return the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
