"""Where JAX keeps its persistent compilation cache for this repo's launchers.

A compiled program is found again only at the same cache path, so the path
is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads that
variable itself, so nothing is configured here), else ``.jax_cache/`` at the
root of the checkout (listed in ``.gitignore``).  Never a temporary,
per-process or time-stamped directory.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compilation_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    # launch/ -> repro/ -> src/ -> checkout root
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call before the first compilation.  With ``JAX_COMPILATION_CACHE_DIR``
    set this changes no setting; otherwise it points JAX at
    :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
