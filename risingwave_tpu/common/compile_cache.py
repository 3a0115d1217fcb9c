"""Where JAX's persistent compilation cache lives — the ONE resolver.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set, and then nothing in
this repository sets another directory in code. Unset, the cache is one
fixed directory inside the checkout (``.jax_cache/``, git-ignored): the
path is part of JAX's cache key, so a directory derived from ``mkdtemp``,
a pid or the time never hits across runs.

Deliberately free of JAX (and of every other import of this package) at
module level: ``bench.py``'s parent process, which must never import
JAX, loads this file by path.

``install_compile_listener`` also makes every compilation visible where
the rest of a barrier is: one ``xla.compile`` span in the trace ring,
stamped with the epoch the conductor is ticking, and ``compiles`` + 1 on
that epoch's barrier-ledger record. A compile inside a steady-state
barrier is a stall someone has to explain.
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


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_listening = False


def install_compile_listener() -> None:
    """Register, once per process, the ``jax.monitoring`` listeners that
    turn each backend compilation into an ``xla.compile`` span (args:
    ``seconds``, ``cache`` = hit / miss / off, ``fun_name``). JAX hands
    the duration over when the compile is done, so the span is recorded
    after the fact, ending now."""
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring

    from . import tracing
    from .barrier_ledger import GLOBAL_STAGES

    cache = "off"        # what the persistent cache said of this compile

    def on_event(event: str, **_kw) -> None:
        nonlocal cache
        if event in _CACHE_EVENTS:
            cache = _CACHE_EVENTS[event]

    def on_duration(event: str, secs: float, **kw) -> None:
        nonlocal cache
        if event != _COMPILE_EVENT:
            return
        epoch = tracing.conductor_epoch()
        dur_ns = int(secs * 1e9)
        tracing.record_span(
            "xla.compile", tracing.now_ns() - dur_ns, dur_ns, epoch=epoch,
            cat=tracing.CAT_DISPATCH, tid="compile", seconds=secs,
            cache=cache, fun_name=str(kw.get("fun_name", "")))
        cache = "off"
        if epoch is not None:
            GLOBAL_STAGES.count(epoch, "compiles")

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
