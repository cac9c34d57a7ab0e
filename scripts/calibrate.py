#!/usr/bin/env python
"""Lattice calibration CLI (ISSUE 16) — measure this deployment's
actual edge bandwidths and persist them as a stamped profile.

Runs the probe suite (``heat_tpu.observability.calibration``): an
on-device copy for ``hbm``, the depth-2 ``device_put`` stream for
``pcie``, a slab read for ``disk``, and tiny per-tier-group all_gather
programs for ``ici``/``dcn`` — each bench.py style (repeat, keep the
floor, flag wide dispersion ``measurement_suspect``). With
``--workload`` it first runs one traced staged pass so the span
ingestion path has real windows to fold in — the same fold a
long-lived deployment gets for free just by running traced.

Prints the constants-vs-measured table and writes the versioned
envelope (sha256 ``profile_id``) to ``--out``. Activate with::

    export HEAT_TPU_LATTICE_PROFILE=/path/to/profile.json

Unset, nothing changes: every price stays the constant and every
plan_id/program stays byte-identical. Exit 0 iff a profile with at
least one measured edge was produced (and saved, when ``--out``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _span_workload() -> None:
    """One traced staged pass over a host-resident operand: populates
    the span buffer with ``stage_in`` windows (tier=pcie, bytes, real
    wall) for the ingestion fold."""
    import numpy as np

    import heat_tpu as ht
    from heat_tpu.observability import tracing
    from heat_tpu.redistribution import staging

    os.environ.setdefault("HEAT_TPU_OOC_SLAB_MB", "8")  # force several windows
    tracing.enable()
    host = staging.HostArray(np.zeros((512, 4096), dtype=np.float32))  # 8 MiB
    ht.linalg.hsvd_rank(host, 8)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", metavar="PATH",
                    help="write the profile envelope JSON here")
    ap.add_argument("--edges", metavar="E[,E...]",
                    help="probe only these edges (default: all five)")
    ap.add_argument("--bytes", type=int, default=None, metavar="N",
                    help="probe payload size (default 32 MiB)")
    ap.add_argument("--repeats", type=int, default=None, metavar="K",
                    help="probe repeats per edge (default 3, floor kept)")
    ap.add_argument("--workload", action="store_true",
                    help="run one traced staged pass first so span "
                         "ingestion has real windows to fold in")
    ap.add_argument("--no-spans", action="store_true",
                    help="probes only; skip span-buffer ingestion")
    ap.add_argument("--platform", help="override the platform stamp")
    ap.add_argument("--topology", help="override the topology stamp")
    ap.add_argument("--json", action="store_true",
                    help="print the envelope JSON instead of the table")
    args = ap.parse_args()

    from heat_tpu.observability import calibration
    from heat_tpu.utils import place_compile_cache

    place_compile_cache()
    if args.workload:
        _span_workload()

    kw = {}
    if args.bytes is not None:
        kw["nbytes"] = args.bytes
    if args.repeats is not None:
        kw["repeats"] = args.repeats
    try:
        profile = calibration.calibrate(
            path=args.out,
            edges=[e.strip() for e in args.edges.split(",")] if args.edges else None,
            include_spans=not args.no_spans,
            platform=args.platform,
            topology=args.topology,
            **kw,
        )
    except RuntimeError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1

    if args.json:
        print(json.dumps(profile, indent=1, sort_keys=True))
    else:
        print(calibration.describe_profile(profile))
    if args.out:
        print(f"# profile {profile['profile_id']} -> {args.out}", file=sys.stderr)
        print(f"# activate: export HEAT_TPU_LATTICE_PROFILE={args.out}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
