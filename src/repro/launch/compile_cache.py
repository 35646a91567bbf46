"""JAX's persistent compilation cache for the entry points.

Called from ``main()`` of ``chip_smoke.py``, ``launch/serve.py`` and
``launch/train.py`` — never at import, so importing the library changes
no global JAX state.
"""
from __future__ import annotations

import os

#: the checkout root (``src/repro/launch/`` -> three levels up)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other path.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (git-ignored): a fixed path, never a
    temp, pid or time-based one, so a later process finds what an
    earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
