"""Hook helpers for instrumenting program caches and hot paths.

The op machinery, the hSVD and the k-cluster fits compile one XLA program
per configuration and memoize it in ``functools.lru_cache``d builders.
Whether a dispatch hit that cache, how long a miss took to build and to
execute first (trace + XLA compile), and what the host pays to launch the
program each time is what a perf investigation needs first, so
``observed_program_cache`` is the decorator those builders carry in place
of a bare ``lru_cache``. Spans (``tracing.span``: on the profiler's trace
under any profiler session, in the ring under ``HEAT_TPU_TRACE``):

- ``ht.program.miss``: a lookup that built; the builder's time;
- ``ht.program.hit``: entered right after a lookup that the cache served
  (the lookup itself, a C dict probe, is in the enclosing span's self time);
- ``ht.program.compile``: the first call of a program after its miss
  (trace + compile + enqueue);
- ``ht.program.launch``: every later call, the host side of the jitted
  call from argument handling to enqueue.

Counters and timers, behind the telemetry switch: ``<name>.hit``,
``<name>.miss``, ``<name>.build``, ``<name>.compile``.

The cache holds the built program behind its launch-spanned proxy, made
once at the miss, so a hit allocates nothing. The wrapper keeps
``cache_clear``/``cache_info``: ``register_mesh_cache`` and tests work on
the wrapped object.
"""

from __future__ import annotations

import functools
import time

from typing import Callable

from . import events as _events
from . import telemetry as _telemetry
from .tracing import span as _span

__all__ = ["nbytes_of", "observed_program_cache"]


def nbytes_of(shape, dtype) -> int:
    """Static byte size of an array from metadata only (trace-safe: never
    touches the buffer)."""
    import numpy as np

    n = 1
    for s in shape:
        n *= int(s)
    try:
        return n * np.dtype(dtype).itemsize
    except TypeError:
        return n * 4


class _Program:
    """What a builder's cache holds: the built program behind the spans
    of its calls. The first call, where ``jax.jit`` traces and XLA
    compiles, is ``ht.program.compile`` (timed under ``<name>.compile``
    when telemetry is on); every later one is ``ht.program.launch``."""

    __slots__ = ("_name", "program", "_looked_up", "_called")

    def __init__(self, name: str, prog: Callable):
        self._name = name
        self.program = prog  # the jitted program itself, for jax.export and AOT stores
        self._looked_up = False  # set by the lookup that built it
        self._called = False

    def __call__(self, *args, **kwargs):
        if self._called:
            with _span("ht.program.launch", cache=self._name):
                return self.program(*args, **kwargs)
        self._called = True
        with _span("ht.program.compile", cache=self._name):
            t0 = time.perf_counter()
            out = self.program(*args, **kwargs)
            dt = time.perf_counter() - t0
        if _telemetry._ENABLED:
            _telemetry.observe(f"{self._name}.compile", dt)
            _events.emit("program_compile", cache=self._name, seconds=round(dt, 6))
        return out

    def __getattr__(self, attr):  # lower()/etc. pass through unspanned
        return getattr(self.program, attr)


def observed_program_cache(name: str, maxsize: int = 128):
    """``functools.lru_cache(maxsize)`` for a program builder, observed:
    the spans and counters of the module docstring under ``name``. The
    builder's result is cached behind a :class:`_Program`, which is what
    every lookup hands back."""

    def deco(builder):
        @functools.lru_cache(maxsize=maxsize)
        def cached(*args, **kwargs):  # runs on a miss only
            with _span("ht.program.miss", cache=name):
                t0 = time.perf_counter()
                prog = builder(*args, **kwargs)
                build_s = time.perf_counter() - t0
            if _telemetry._ENABLED:
                _telemetry.inc(f"{name}.miss")
                _telemetry.observe(f"{name}.build", build_s)
            return _Program(name, prog)

        @functools.wraps(builder)
        def lookup(*args, **kwargs):
            prog = cached(*args, **kwargs)
            if prog._looked_up:
                with _span("ht.program.hit", cache=name):
                    pass
                if _telemetry._ENABLED:
                    _telemetry.inc(f"{name}.hit")
            else:
                prog._looked_up = True
            return prog

        lookup.cache_clear = cached.cache_clear
        lookup.cache_info = cached.cache_info
        return lookup

    return deco
