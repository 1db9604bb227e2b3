"""Where this program keeps JAX's persistent compilation cache.

``use_compile_cache()`` is called from the ``main()`` of each launcher
(``chip_smoke.py``, ``launch/serve.py``, ``launch/mbe_run.py``) and never
at import.  A cache entry is keyed by its path among other things, so the
directory must be stable from one run to the next:

* ``JAX_COMPILATION_CACHE_DIR`` set — that directory, and no other;
* unset — ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
