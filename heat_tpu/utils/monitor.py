"""Tracing / profiling instrumentation — compat shim over
``heat_tpu.observability``.

The reference instruments its continuous benchmarks with the external
``perun`` energy/runtime monitor (``@monitor()`` decorators,
reference benchmarks/cb/linalg.py:4-23); the library itself ships no
profiler. This module keeps the perun-shaped surface (``monitor`` /
``report`` / ``reset`` / ``trace``) for ported ``benchmarks/cb``
scripts, but since the observability subsystem landed it is a THIN
SHIM: timings go into a dedicated
:class:`heat_tpu.observability.telemetry.Registry` (always on — the
decorator is explicit opt-in, independent of the global
``HEAT_TPU_TELEMETRY`` switch), and ``report()`` renders that
registry's statistics — call counts, totals, best, mean AND p50/p95,
which the old standalone implementation could not provide. The backing
registry is sharded per recording thread (ISSUE 9: the serving
dispatcher's worker and its client threads record concurrently), so
``@monitor``-ed functions called from many threads never serialize on
one lock and the reported totals stay exact. For first-party metrics
(collective counts, reshard bytes, cache hits) use ``ht.telemetry`` /
``ht.observability`` directly.

Energy (the perun-parity deviation, explicit per VERDICT r4 #8): perun
reads RAPL/NVML counters on the reference's CPU/GPU hosts. This
platform exposes NO per-process energy counter — TPU power telemetry
lives in the cloud monitoring plane (``tpu.googleapis.com`` duty-cycle /
watts metrics), not in any in-container API, and the jax profiler
reports time/bytes/FLOPs but not joules. ``@monitor`` therefore records
runtime only; for energy estimates, multiply device-seconds by the
chip's published TDP envelope (v5e: ~170-250 W/chip depending on
workload class) or read the fleet metrics externally. docs/PERF.md
carries the same note next to the benchmark table.
"""

from __future__ import annotations

import contextlib
import functools
import json
import time

from typing import Any, Callable, Optional

import jax

from ..observability import telemetry as _telemetry

__all__ = ["monitor", "report", "reset", "trace"]

# dedicated always-on registry: decorating a function IS the opt-in, so
# @monitor timings must not depend on the global telemetry switch
_REGISTRY = _telemetry.Registry()


def _blockable(out):
    """Unwrap DNDarray leaves to their jax arrays: jax.block_until_ready
    treats a DNDarray as an opaque pytree leaf and returns immediately,
    which would make device work look free."""
    from ..core.dndarray import DNDarray

    if isinstance(out, DNDarray):
        return out._phys
    if isinstance(out, dict):
        return {k: _blockable(v) for k, v in out.items()}
    if isinstance(out, (list, tuple)):
        return [_blockable(v) for v in out]
    return out


def monitor(name: Optional[str] = None, sync: bool = True):
    """Decorator recording per-call wall time under ``name`` (defaults to
    the function name) — the shape of perun's ``@monitor()`` used by the
    reference's continuous benchmarks.

    ``sync=True`` blocks on jax array outputs before stopping the clock,
    so asynchronous dispatch doesn't make device work look free. When the
    global telemetry switch is on, each call is mirrored as a
    ``monitor.<name>`` timer in the process-wide registry too, so
    ``@monitor``-ed workloads land in the same export as the first-party
    metrics.
    """

    def deco(fn: Callable) -> Callable:
        key = name or fn.__name__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            if sync:
                try:
                    jax.block_until_ready(_blockable(out))
                except (TypeError, ValueError):
                    # non-blockable output structure; device-execution
                    # errors must propagate, not be recorded as timings
                    pass
            dt = time.perf_counter() - t0
            _REGISTRY.observe(key, dt)
            _telemetry.observe(f"monitor.{key}", dt)  # no-op unless enabled
            return out

        return wrapper

    return deco


def report(as_json: bool = False) -> Any:
    """Accumulated monitor table:
    ``{name: {calls, total_s, best_s, mean_s, max_s, p50_s, p95_s}}``
    (the old report carried totals only; call counts and percentiles
    come from the registry's sample reservoir)."""
    table = _REGISTRY.timer_table()
    if as_json:
        return json.dumps(table)
    return table


def reset() -> None:
    """Clear the monitor registry."""
    _REGISTRY.clear()


@contextlib.contextmanager
def trace(path: str):
    """Capture a jax.profiler trace (Perfetto/XPlane) of the enclosed
    block to ``path`` — view in TensorBoard or ui.perfetto.dev. The
    TPU-side story the reference delegates to perun's energy counters.

    The trace holds heat_tpu's own spans beside the device ops, on one
    clock, with nothing to enable: ``ht.call.*`` (the phases of
    ``hsvd_rank`` and ``KMeans.fit``), ``ht.op.*`` (one per eager op),
    ``ht.program.hit``/``miss``/``launch``/``compile`` (every program
    builder's cache and the host side of each jitted call, with the
    builder as ``cache=``) and ``ht.comm.place``/``shard``/``reshard``.
    They are on the thread lines of the ``/host:CPU`` plane; the device
    ops are on ``/device:TPU:<i>``. ``docs/API.md`` (observability)
    lists every name."""
    jax.profiler.start_trace(path)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
