#!/usr/bin/env python
"""Metrics/trace dump CLI (ISSUE 15) — the exposition surface for a
process that has no HTTP endpoint of its own.

Three output forms over one small workload (or an importing caller's
already-live registry when ``--no-workload``):

- default: Prometheus text format (``ht.observability.prometheus_text``)
  — registry counters as ``_total``, timers as summaries with
  p50/p95/p99 quantile labels, event-ring + flight-recorder health
  (``heat_tpu_flight_dropped_total``), and per-dispatcher gauges when
  the serving layer is live;
- ``--json``: the raw ``telemetry.snapshot()`` (counters, timers, event
  ring metadata) as one JSON document;
- ``--trace PATH``: additionally export the span buffer as Chrome
  trace-event JSON (``ht.observability.export_trace``), loadable in
  Perfetto/chrome://tracing.

The built-in workload runs one planned redistribution with telemetry +
tracing enabled, so the smoke leg exercises the whole pipeline: spans
recorded -> counters rendered -> trace exported. Exit 0 iff every
requested output was produced and parses.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _workload() -> None:
    """One planned redistribution + a tiny reduction: enough to light up
    op/program counters, redistribution spans, and the event ring."""
    import heat_tpu as ht

    x = ht.arange(4096, split=0).astype(ht.float32)
    y = x.reshape((64, 64)).resplit(1)
    ht.sum(y).numpy()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true",
                    help="emit telemetry.snapshot() JSON instead of Prometheus text")
    ap.add_argument("--trace", metavar="PATH",
                    help="also export the span buffer as Chrome-trace JSON to PATH")
    ap.add_argument("--no-workload", action="store_true",
                    help="dump whatever is already collected; run nothing")
    args = ap.parse_args()

    from heat_tpu.observability import telemetry, tracing
    import heat_tpu.observability as obs
    from heat_tpu.utils import place_compile_cache

    place_compile_cache()
    if not args.no_workload:
        telemetry.enable()  # tracing follows at HEAT_TPU_TRACE=auto
        _workload()

    if args.json:
        print(json.dumps(telemetry.snapshot(), indent=1, sort_keys=True, default=str))
    else:
        sys.stdout.write(obs.prometheus_text())

    if args.trace:
        n = obs.export_trace(args.trace)
        with open(args.trace) as f:
            doc = json.load(f)  # must round-trip as valid JSON
        if doc.get("traceEvents") is None or len(doc["traceEvents"]) != n:
            raise SystemExit(
                f"trace export mismatch: {args.trace} holds "
                f"{len(doc.get('traceEvents') or [])} events, expected {n}"
            )
        print(f"# trace: {n} events -> {args.trace} "
              f"({len(tracing.spans())} spans, dropped={tracing.dropped()})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
