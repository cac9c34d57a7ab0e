"""First-party runtime observability (reference deviation: the reference
delegates ALL instrumentation to external tools — perun around its
benchmark scripts, nothing inside the library).

Four pieces, one import surface:

- :mod:`~heat_tpu.observability.telemetry` — process-wide counters,
  timers (p50/p95/p99), the ``record()`` context manager, and
  :func:`prometheus_text` exposition; zero-cost when disabled,
  ``HEAT_TPU_TELEMETRY=1`` or ``enable()`` to activate. Also exposed
  as the ``ht.telemetry`` shorthand.
- :mod:`~heat_tpu.observability.events` — bounded structured event log
  fed by the hooks in ``core/`` (shard/reshard bytes, program-cache
  misses, ``ht.jit`` traces); overwrites counted, span-correlated.
- :mod:`~heat_tpu.observability.tracing` — span tracing of the hot
  layers (``ht.tracing.span``): every span is an
  annotation of ``jax.profiler``'s trace, so under any profiler session
  (``ht.utils.monitor.trace(path)``) the program's ``ht.call.*``,
  ``ht.op.*``, ``ht.program.*`` and ``ht.comm.*`` spans sit beside the
  device ops in the profiler's trace; with ``HEAT_TPU_TRACE`` on a span
  is also a parented record in the module's ring (Chrome-trace
  export :func:`export_trace`). The always-on flight
  recorder lives there too. ``HEAT_TPU_TRACE`` is registered
  ``affects_programs=False`` — plans, plan_ids, programs, and AOT keys
  are byte-identical at every value, and with or without a session.
- :mod:`~heat_tpu.observability.hlo` — :func:`collective_counts`, the
  compile-only HLO inspector pinning each op's collective structure
  (the public form of the MULTICHIP dryrun asserts).

Instrumentation glue for the core layers lives in
:mod:`~heat_tpu.observability.instrument` (not re-exported): the
``observed_program_cache`` decorator of every program builder.
"""

from . import events
from . import hlo
from . import instrument
from . import telemetry
from . import tracing

from .hlo import COLLECTIVE_OPS, CollectiveReport, collective_counts
from .telemetry import (
    disable,
    enable,
    enabled,
    export_jsonl,
    inc,
    observe,
    prometheus_text,
    record,
    report,
    reset,
    snapshot,
)
from .tracing import export_trace, flight_tail, span

__all__ = [
    "COLLECTIVE_OPS",
    "CollectiveReport",
    "collective_counts",
    "disable",
    "enable",
    "enabled",
    "export_jsonl",
    "export_trace",
    "flight_tail",
    "inc",
    "observe",
    "prometheus_text",
    "record",
    "report",
    "reset",
    "snapshot",
    "span",
]
