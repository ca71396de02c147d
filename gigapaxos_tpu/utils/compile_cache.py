"""Where JAX's persistent compilation cache lives.

One rule for every entry point that can touch the chip
(``chip_smoke.py``, ``bench.py``, ``probe.py``,
``reconfigurable_node.main``, ``serving.worker.main``): when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and this
code sets nothing; otherwise the cache sits at ``<checkout>/.jax_cache``.
The directory is part of the cache key, so it is a fixed path — never a
temporary name, a pid or a time — and two processes started from two
working directories find each other's entries.
"""

from __future__ import annotations

import os
from typing import Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# gigapaxos_tpu/utils/compile_cache.py -> the checkout root
CHECKOUT_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT_DIR, ".jax_cache")


def configure_compile_cache() -> Optional[str]:
    """Place the compile cache; returns the directory this call set, or
    None when the environment placed it (nothing is set in code then).
    Call before the first compile."""
    if os.environ.get(CACHE_ENV):
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
