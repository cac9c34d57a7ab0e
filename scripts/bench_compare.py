#!/usr/bin/env python
"""Bench regression gate.

VERDICT r5 caught an attention-MFU regression (0.68 -> 0.58 run-over-run)
that nothing in the repo flagged: bench.py checks each run against
PHYSICAL bounds, but nothing compared a run against the PREVIOUS run.
This script closes that gap: it diffs the current bench record
(``BENCH_DETAIL.json``) against a baseline (default: the highest-numbered
``BENCH_r*.json`` driver artifact in the repo root), flags every shared
metric that moved more than ``--threshold`` (default 10%) in the BAD
direction without a ``measurement_suspect`` marker on either side, and
emits ONE machine-readable verdict line plus ``BENCH_COMPARE.json`` —
so a perf regression is caught at PR time instead of by the round judge.
Rows only one side knows about never gate or crash the diff: a
benchmark new in the current record reports as ``new_row``, one the
baseline had but the current run dropped as ``missing_row``.

Exit code is 0 unless ``--strict`` is given and an unflagged regression
was found (CI runs report-only; a bench-carrying PR should run
``--strict``).

Usage::

    python scripts/bench_compare.py                 # auto-pick files
    python scripts/bench_compare.py --strict        # gate (nonzero exit)
    python scripts/bench_compare.py --baseline BENCH_r05.json --threshold 0.15
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# metric -> direction. Rows/fields not listed are informational and
# never gate (method strings, passes_over_A, ordering_ok, ...).
HIGHER_IS_BETTER = {
    "value",
    "vs_baseline",
    "vs_torch_svd_lowrank",
    "mfu",
    "tflops",
    "gbps",
    # `hbm_frac` gates the ROADMAP reshape acceptance fields too
    # (ISSUE 5): `reshape_split1_1gb.hbm_frac` and the lane-friendly
    # companion `reshape_lane_1gb.hbm_frac` ride in the compact
    # key_rows, so driver artifacts carry them round over round (the
    # string-valued `path`/`strategy` fields are informational)
    "hbm_frac",
    "hbm_frac_algorithmic",
    "iter_per_s",
    "projected_iter_per_s_1Bx64_v5e64",
    "melem_per_s",
    "speedup_vs_torch_cpu",
    "speedup_vs_torch_svd_lowrank",
    # sort-row acceptance fields (ISSUE 4): public fused sort vs raw
    # values-only jnp.sort, and achieved fraction of the dispatched
    # path's pass-count HBM model (heat_tpu.kernels.sort.sort_plan)
    "vs_jnp_sort",
    "sort_frac",
    # overlap acceptance fields (ISSUE 6) on the redistribution rows:
    # `critical_path_model` is the planner's modeled max-vs-sum speedup
    # of the pipelined stage groups, `vs_sequential` the measured
    # same-run ratio against the HEAT_TPU_REDIST_OVERLAP=0 twin — both
    # ride in the compact key_rows so driver artifacts gate them
    "critical_path_model",
    "vs_sequential",
    # wire-quantization acceptance field (ISSUE 7): the analytic
    # v5e-64 quantized-gradient DP model's step-time speedup
    # (dp_step_quant row; tests pin >= 1.5x on ICI-bound layers)
    "dp_model_speedup",
    # two-tier acceptance field (ISSUE 8): hierarchical-vs-flat modeled
    # speedup of the `*_2x8_dcn` rows (tests pin >= 2x; dp_step_quant_2x8
    # reuses dp_model_speedup)
    "tier_model_speedup",
    # serving acceptance field (ISSUE 9): sustained micro-batched QPS
    # (serving_qps row)
    "qps",
    # out-of-core staging acceptance fields (ISSUE 11) on the
    # `*_hostram`/`kmeans_stream_2gb` rows: achieved fraction of the
    # depth-2 staging bound (tests pin >= 0.5; ~1.0 on real PCIe DMA),
    # the analytic lattice throughput of the 20 GB scenario, and the
    # measured streamed GB/s (`gbps` above covers the measured rows)
    "stage_bw_frac",
    "stage_model_gbps",
    "rows_per_s",
    # resilience acceptance fields (ISSUE 13) on the ckpt_write_2gb
    # row: durable slab-streamed commit throughput and its fraction of
    # the lattice's host->disk durable-commit bound (floor 0.5 pinned)
    "write_gbps",
    "bound_frac",
    # dense-factorization acceptance field (ISSUE 19): the solver's
    # flop rate over the SAME-RUN reference GEMM's rate (polar_2gb's
    # floor is 0.5 — the bare GEMM is the ceiling by construction; the
    # polar_2gb/eig_2gb `mfu` fields gate via `mfu` above, and the
    # analytic 200 GB v5e-64 `model_*` fields hard-gate via ci.sh's
    # --unchanged-fields sweep like every other analytic model output)
    "frac_of_matmul",
    # sparse-engine acceptance fields (ISSUE 18): spmm_1gb's achieved
    # fraction of the lattice's nnz-weighted wire-mass floor (>= 0.5
    # pinned on CPU) and its same-run dense-matmul-twin ratio; the
    # pagerank_2m scenario's edge throughput (`gbps` above covers the
    # nnz-bandwidth figure itself)
    "nnz_bw_frac",
    "vs_dense_matmul",
    "edges_per_s",
}

# rows that changed name across rounds: a baseline row under the old
# name gates against the current row under the new one (PR 4 folded the
# legacy `reshape` detail row — which still carried the pre-planner
# 0.084 hbm_frac in old artifacts — into the planner-attributed
# `reshape_split1_1gb` row; both always measured the same workload)
ROW_RENAMES = {"reshape": "reshape_split1_1gb"}
LOWER_IS_BETTER = {
    "seconds",
    "seconds_unrounded",
    "eager_wallclock_s",
    "overhead_vs_raw_jnp",
    "overhead_vs_fused_jnp",
    # the kernel-ring wrapper cost relative to bare splash: growth is a
    # real regression (bench.py flags <0.9 samples as weather)
    "vs_splash_row",
    # ISSUE 7: encoded/raw wire bytes of the executing plan on the
    # gated redistribution rows (and the dp_step_quant model row) —
    # a ratio drifting back toward 1.0 means the codec disengaged
    "wire_ratio",
    # ISSUE 8: per-device bytes the tiered plans route over the
    # expensive tier — growth means movement regressed onto DCN
    "dcn_bytes",
    # ISSUE 9: per-request p95 latency of the serving_qps row
    "p95_s",
    # ISSUE 10: memcheck's static per-device peak-HBM estimate of the
    # gated redistribution programs (ht.analysis.memcheck) — growth
    # means a planner/executor change inflated the live set, caught
    # pre-TPU (the xla_* cross-check fields are informational: the
    # compiler's buffer assignment moves with XLA versions)
    "static_peak_bytes",
    # ISSUE 13: the recovery_resume row's detect→drain→rekey→restore
    # wall-clock (and the resumed replay) — growth means the failover
    # control plane slowed down
    "recovery_s",
    "resume_s",
    # ISSUE 16: mean |model_error| over an attribution-carrying row's
    # priced legs — growth means the cost model's fidelity regressed;
    # the calibrated column's mean must land at or below the constants
    # figure (the ci.sh calibration leg's shrinkage gate)
    "mean_abs_model_error",
    "mean_abs_calibrated_error",
    # ISSUE 19: cholesky_2gb's measured seconds over its matmul-count
    # time model (n³/3 flops at the same-run reference GEMM rate) —
    # the acceptance bound is <= 2.0; growth means the ring-lookahead
    # pipeline regressed against the matmuls it is made of
    "vs_matmul_count",
    # ISSUE 18: pagerank_2m's iterations-to-tol — deterministic for the
    # seeded graph, so growth means an engine numerics change slowed
    # the fixpoint, not weather
    "iterations",
}


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _rows_of(record: dict) -> dict:
    """Normalize any of the three record shapes to {row: {field: num}}.

    - BENCH_DETAIL.json: {"detail": {row: {...}}, "value": ...}
    - driver BENCH_r0N.json: {"parsed": <compact line>} with
      parsed.key_rows
    - a compact line itself: {"key_rows": {...}, "value": ...}
    """
    if "parsed" in record and isinstance(record.get("parsed"), dict):
        record = record["parsed"]
    rows = {}
    if isinstance(record.get("detail"), dict):
        rows.update({k: dict(v) for k, v in record["detail"].items()})
    elif isinstance(record.get("key_rows"), dict):
        rows.update({k: dict(v) for k, v in record["key_rows"].items()})
    if isinstance(record.get("value"), (int, float)):
        rows["_headline"] = {"value": record["value"]}
    return rows


def _latest_round_artifact() -> str | None:
    best, best_n = None, -1
    for path in glob.glob(os.path.join(ROOT, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if m and int(m.group(1)) > best_n:
            best, best_n = path, int(m.group(1))
    return best


def compare(current: dict, baseline: dict, threshold: float) -> dict:
    cur_rows, base_rows = _rows_of(current), _rows_of(baseline)
    # rename handling: re-key baseline rows whose name the bench retired,
    # unless the baseline already carries the new name too
    for old, new in ROW_RENAMES.items():
        if old in base_rows and new not in base_rows:
            base_rows[new] = base_rows.pop(old)
        if old in cur_rows and new not in cur_rows:
            cur_rows[new] = cur_rows.pop(old)
    regressions, improvements, compared = [], [], 0
    # rows only one side knows about never gate: a brand-new benchmark
    # (in BENCH_DETAIL.json but not yet in any BENCH_r*.json artifact)
    # is reported as new_row — it has no baseline to regress against —
    # and a row the baseline had but the current run dropped is
    # missing_row (usually a renamed bench; worth eyes, not a gate)
    new_rows = sorted(set(cur_rows) - set(base_rows))
    missing_rows = sorted(set(base_rows) - set(cur_rows))
    for row, base_fields in sorted(base_rows.items()):
        cur_fields = cur_rows.get(row)
        if cur_fields is None:
            continue
        suspect = bool(
            cur_fields.get("measurement_suspect") or base_fields.get("measurement_suspect")
        )
        for field, base_val in sorted(base_fields.items()):
            if field in HIGHER_IS_BETTER:
                sign = 1.0
            elif field in LOWER_IS_BETTER:
                sign = -1.0
            else:
                continue
            cur_val = cur_fields.get(field)
            if not isinstance(cur_val, (int, float)) or not isinstance(base_val, (int, float)):
                continue
            if base_val == 0:
                continue
            compared += 1
            # relative move in the GOOD direction (negative = got worse)
            rel = sign * (cur_val - base_val) / abs(base_val)
            entry = {
                "row": row,
                "field": field,
                "baseline": base_val,
                "current": cur_val,
                "rel_change": round(rel, 4),
            }
            if rel < -threshold:
                if suspect:
                    entry["waived"] = "measurement_suspect"
                regressions.append(entry)
            elif rel > threshold:
                improvements.append(entry)
    gating = [r for r in regressions if "waived" not in r]
    return {
        "verdict": "regressed" if gating else "ok",
        "threshold": threshold,
        "compared": compared,
        # suspect-flagged moves are excluded from the gate but COUNTED:
        # a waived regression is data for eyes (re-run the bench), not
        # silence — the r5 attention-MFU slip must stay visible
        "waived": len(regressions) - len(gating),
        "regressions": regressions,
        "improvements": improvements,
        "new_rows": new_rows,
        "missing_rows": missing_rows,
    }


def unchanged_check(current: dict, baseline: dict, pattern: str) -> dict:
    """Exact-equality guard over DETERMINISTIC fields (ISSUE 12): fields
    matching ``pattern`` are analytic-model outputs (``*model*`` speedups,
    planned byte counts, wire ratios) that a pure refactor — e.g. the
    gate-registry move — must reproduce bit-for-bit; any drift means the
    refactor changed a plan or a price, not just plumbing. Rows only one
    side has are skipped (the threshold compare reports those)."""
    rx = re.compile(pattern)
    cur_rows, base_rows = _rows_of(current), _rows_of(baseline)
    mismatches, held = [], 0
    for row, base_fields in sorted(base_rows.items()):
        cur_fields = cur_rows.get(row)
        if cur_fields is None:
            continue
        for field, base_val in sorted(base_fields.items()):
            if not rx.search(field) or not isinstance(base_val, (int, float)):
                continue
            cur_val = cur_fields.get(field)
            if not isinstance(cur_val, (int, float)):
                continue
            if cur_val == base_val:
                held += 1
            else:
                mismatches.append(
                    {"row": row, "field": field, "baseline": base_val, "current": cur_val}
                )
    return {
        "verdict": "moved" if mismatches else "unchanged",
        "pattern": pattern,
        "held": held,
        "mismatches": mismatches,
    }


def run(current_path=None, baseline_path=None, threshold=0.10, out_path=None,
        unchanged_fields=None) -> dict:
    """Library entry (bench.py calls this after writing BENCH_DETAIL.json).
    ``unchanged_fields`` (a regex) additionally runs the exact-equality
    guard and persists its verdict in the written BENCH_COMPARE.json."""
    current_path = current_path or os.path.join(ROOT, "BENCH_DETAIL.json")
    baseline_path = baseline_path or _latest_round_artifact()
    if baseline_path is None or not os.path.exists(current_path):
        return {
            "verdict": "skipped",
            "reason": "missing bench artifacts",
            "current": current_path,
            "baseline": baseline_path,
        }
    current, baseline = _load(current_path), _load(baseline_path)
    result = compare(current, baseline, threshold)
    result["current_file"] = os.path.relpath(current_path, ROOT)
    result["baseline_file"] = os.path.relpath(baseline_path, ROOT)
    if unchanged_fields:
        result["unchanged_fields"] = unchanged_check(
            current, baseline, unchanged_fields
        )
    if out_path is None:
        out_path = os.path.join(ROOT, "BENCH_COMPARE.json")
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--current", default=None, help="bench record (default BENCH_DETAIL.json)")
    ap.add_argument(
        "--baseline", default=None, help="baseline record (default: latest BENCH_r*.json)"
    )
    ap.add_argument("--threshold", type=float, default=0.10, help="relative move that gates")
    ap.add_argument(
        "--strict", action="store_true", help="exit 1 on an unflagged regression"
    )
    ap.add_argument(
        "--unchanged-fields",
        default=None,
        metavar="REGEX",
        help="additionally require fields matching REGEX to be EXACTLY "
        "equal between current and baseline (deterministic model fields; "
        "exit 1 on any drift) — the pure-refactor guard",
    )
    args = ap.parse_args()
    result = run(
        args.current, args.baseline, args.threshold,
        unchanged_fields=args.unchanged_fields,
    )
    unchanged = result.get("unchanged_fields")
    # one compact machine-readable line on stdout (details in BENCH_COMPARE.json)
    compact = {
        "verdict": result["verdict"],
        "threshold": result.get("threshold"),
        "compared": result.get("compared"),
        "regressed": [
            f"{r['row']}.{r['field']}" for r in result.get("regressions", []) if "waived" not in r
        ],
        "waived": [
            f"{r['row']}.{r['field']}" for r in result.get("regressions", []) if "waived" in r
        ],
        "improved": [f"{r['row']}.{r['field']}" for r in result.get("improvements", [])],
        "new_row": result.get("new_rows", []),
        "missing_row": result.get("missing_rows", []),
        "baseline_file": result.get("baseline_file") or result.get("baseline"),
    }
    if unchanged is not None:
        compact["unchanged_fields"] = {
            "verdict": unchanged["verdict"],
            "held": unchanged["held"],
            "moved": [f"{m['row']}.{m['field']}" for m in unchanged["mismatches"]],
        }
    print(json.dumps(compact))
    if unchanged is not None and unchanged["verdict"] == "moved":
        return 1
    return 1 if (args.strict and result["verdict"] == "regressed") else 0


if __name__ == "__main__":
    sys.exit(main())
