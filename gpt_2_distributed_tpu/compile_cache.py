"""Where JAX's persistent compilation cache lives.

Every entry point (train, sample, serve, the HTTP front door, the worker)
calls :func:`ensure_compile_cache` before it builds a program, so that a
second process compiling the same program — a ``--resume`` run, a respawned
worker, the next benchmark cell — reads it back instead of compiling cold.

The directory is placed from outside: where ``JAX_COMPILATION_CACHE_DIR`` is
set JAX reads it itself and this module sets nothing. Otherwise the cache
goes to one fixed directory at the root of the checkout. The path is part
of how a cache is found again, so it never derives from a temp dir, a pid
or the clock.

Lives at the package root, not under ``utils/`` (whose ``__init__`` imports
jax): the front-door parents that stay off jax call it too.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache",
)


def ensure_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere stable; return
    the directory in effect. Idempotent, and safe before or after
    ``import jax``."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    # Exported rather than only configured: worker and suite children
    # inherit it and land in the same directory as their parent.
    os.environ[ENV_VAR] = DEFAULT_CACHE_DIR
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read the (then unset) variable at import; tell it directly.
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
