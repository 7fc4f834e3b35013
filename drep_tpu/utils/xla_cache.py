"""Persistent XLA compilation cache, on by default.

Every compare, index build and serve start compiles the same handful of
(shape, program) pairs; JAX's persistent cache lets every process after
the first reuse them. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads
it itself and this module sets nothing. Otherwise the cache lives at ONE
fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored): the
path is part of the cache key, so a directory named after a pid, a time
or a temp dir would never hit, and a fixed in-checkout path is what lets
every child process of one run share compiled programs.
"""

from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_done = False


def enable_persistent_cache() -> None:
    global _done
    if _done:
        return
    _done = True
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return  # placed from outside: JAX reads the variable itself
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
