"""JAX's persistent compilation cache, pointed at one fixed directory.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
call ``enable_compile_cache`` once, before they compile anything; importing
``repro`` sets nothing.  ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's
own setting and wins untouched.  Otherwise the cache lives at
``<checkout>/.jax_cache``, one fixed path per checkout, so every later run
from the same checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    # the served kernels compile in under JAX's default 1 s threshold, and
    # a run compiles dozens of them: keep every program
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return str(DEFAULT_DIR)
