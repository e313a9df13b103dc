"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles the same programs run after
run (`chip_smoke.py`, `bench.py`, the tools' multi-process smokes): the
directory comes from outside when the caller names one, and is otherwise
a FIXED path inside the checkout. A cache under a `mkdtemp`, a pid or a
timestamp is a new directory every run and never hits.
"""
from __future__ import annotations

import os

__all__ = ["configure"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure() -> str:
    """Place the persistent compile cache and return its directory. Call
    before the first compile.

    With `JAX_COMPILATION_CACHE_DIR` set, JAX reads the variable itself
    and nothing is set in code; otherwise the cache goes to
    `<checkout>/.jax_cache` (git-ignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
