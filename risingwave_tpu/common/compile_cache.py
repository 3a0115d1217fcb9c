"""Where JAX's persistent compilation cache lives — the ONE resolver.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set, and then nothing in
this repository sets another directory in code. Unset, the cache is one
fixed directory inside the checkout (``.jax_cache/``, git-ignored): the
path is part of JAX's cache key, so a directory derived from ``mkdtemp``,
a pid or the time never hits across runs.

Deliberately free of JAX (and of every other import of this package) at
module level: ``bench.py``'s parent process, which must never import
JAX, loads this file by path.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The cache directory: the environment's if set, else the fixed
    in-checkout path (identical across calls and processes)."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def export_compile_cache_env() -> str:
    """For parents that spawn JAX children: put the resolved directory
    into the environment the children inherit. Returns it."""
    os.environ.setdefault(_ENV, compile_cache_dir())
    return os.environ[_ENV]


def enable_compile_cache() -> str:
    """For a process that compiles itself: point JAX at the resolved
    directory. With the variable set JAX has already read it, and no
    directory is set here. Returns the directory in use."""
    import jax

    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    return compile_cache_dir()
