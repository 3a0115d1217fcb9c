"""Jit-dispatch counting — the regression guard for the dispatch ladder.

The whole point of the fused epoch surfaces (ops/fused_epoch.py,
docs/performance.md) is that ONE jitted call covers an entire epoch of
ingest; the historical failure mode is an edit that quietly reintroduces a
per-chunk call ladder (k dispatches per epoch, each a host→device
launch). XLA offers no portable "how many times was an executable
launched" hook across backends, so the counter sits one
level up, where the ladder actually manifests: every function produced by
``jax.jit`` is wrapped to count its *calls from host control flow* (calls
inside a trace never re-enter the Python wrapper, so fused inner steps
correctly count zero).

Usage::

    with count_dispatches() as c:
        ...build pipeline + run...
    assert c.counts["fused_source_agg_epoch.<locals>.epoch"] == n_epochs

Only functions jitted WHILE the context is active are counted — build the
pipeline inside the ``with`` block. Not thread-safe (patches ``jax.jit``);
tests only.
"""

from __future__ import annotations

import contextlib
import functools
from collections import Counter

import jax


class DispatchCounter:
    def __init__(self):
        self.counts: Counter = Counter()

    @property
    def total(self) -> int:
        return sum(self.counts.values())

    def reset(self) -> None:
        self.counts.clear()

    def record(self, name: str) -> None:
        self.counts[name] += 1


@contextlib.contextmanager
def count_dispatches():
    counter = DispatchCounter()
    orig_jit = jax.jit

    def counting_jit(fun=None, **kwargs):
        if fun is None:    # jax.jit(static_argnums=...) decorator form
            return functools.partial(counting_jit, **kwargs)
        jitted = orig_jit(fun, **kwargs)
        name = getattr(fun, "__qualname__",
                       getattr(fun, "__name__", repr(fun)))

        @functools.wraps(fun)
        def wrapper(*a, **k):
            counter.record(name)
            return jitted(*a, **k)

        # keep the AOT surface available through the wrapper
        wrapper.lower = jitted.lower
        wrapper.trace = getattr(jitted, "trace", None)
        wrapper.__wrapped_jit__ = jitted
        return wrapper

    jax.jit = counting_jit
    try:
        yield counter
    finally:
        jax.jit = orig_jit
