#!/usr/bin/env python
"""Serving warmup CLI — pre-compile and export the declared program set.

A fleet rollout runs this ONCE per (jax version, heat_tpu version,
platform, device count, env-gate combination) and ships the resulting
cache directory with the image; every serving replica then cold-starts
load-not-compile (``heat_tpu.serving.aot_cache``). The declared set is
``heat_tpu.serving.WARMUP_PROGRAMS`` — estimator predict programs at
their bucket shapes plus the representative ``ht.jit`` pipeline.

Usage::

    python scripts/warmup.py --cache-dir /var/cache/heat_tpu
    python scripts/warmup.py --list
    python scripts/warmup.py --cache-dir DIR --programs kcluster_predict
    python scripts/warmup.py --cache-dir DIR --expect-hits   # reload smoke

``--expect-hits`` exits nonzero unless EVERY declared program came back
from the store (the cross-process cache-hit proof the CI serving leg
pins: a fresh process compiles 0 programs).

Exit code 0 on success; one JSON summary line on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--cache-dir", default=None,
                    help="store root (default: HEAT_TPU_SERVING_CACHE or ~/.cache/heat_tpu/aot)")
    ap.add_argument("--programs", default=None,
                    help="comma-separated subset of the declared set (default: all)")
    ap.add_argument("--list", action="store_true", help="list the declared set and exit")
    ap.add_argument("--expect-hits", action="store_true",
                    help="exit 1 unless every program loaded from the store (reload smoke)")
    args = ap.parse_args()

    # gate resolution must happen before the heat_tpu import
    os.environ.setdefault("HEAT_TPU_SERVING_AOT", "1")
    if args.cache_dir:
        os.environ["HEAT_TPU_SERVING_CACHE"] = args.cache_dir

    import heat_tpu as ht

    ht.utils.place_compile_cache()
    if args.list:
        print(json.dumps({"programs": sorted(ht.serving.WARMUP_PROGRAMS)}))
        return 0

    if not ht.serving.enabled():
        print(json.dumps({"error": "serving AOT cache disabled (HEAT_TPU_SERVING_AOT=0?)"}))
        return 1

    names = args.programs.split(",") if args.programs else None
    results = ht.serving.warmup(names)
    store = ht.serving.active_store()
    statuses = [s for v in results.values() for s in v["variants"].values()]
    summary = {
        "cache_dir": store.root,
        "programs": results,
        "stats": store.stats,
        "entries": len(store.entries()),
        "all_hits": bool(statuses) and all(s == "hit" for s in statuses),
    }
    print(json.dumps(summary))
    if args.expect_hits and not summary["all_hits"]:
        print("[warmup] --expect-hits: at least one program was not served "
              "from the store", file=sys.stderr)
        return 1
    if not statuses or any(s in ("off", "bypass") for s in statuses):
        print("[warmup] warning: some programs bypassed the store", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
