#!/usr/bin/env bash
# CI contract — the analog of the reference's test matrix
# (/root/reference/.github/workflows/ci.yaml:54-56: `mpirun -n 3/4 pytest`,
# deliberately one even AND one odd world to catch divisibility bugs).
#
# One command reproduces the full evidence:
#  1. the whole suite on a virtual 8-device CPU mesh (tests/conftest.py
#     forces JAX_PLATFORMS=cpu + xla_force_host_platform_device_count=8),
#     which includes the REAL 2x2- and 4x1-process Gloo worlds
#     (tests/test_multiprocess.py) covering ingest, saves, sort,
#     percentile, ring attention, KMeans, compaction ops, DP + DASO;
#  2. the ODD-mesh leg (VERDICT r4 #6): the suite again at 5 devices —
#     where chunk geometry, DASO node factorization, and every
#     p-divisibility assumption degenerate differently — with the slow
#     marks and the (process-spawning, mesh-size-independent)
#     multiprocess worlds excluded;
#  3. the telemetry-enabled smoke leg: the instrumentation hooks
#     (program-cache counters, shard/reshard events, ht.jit tracing)
#     must add NO failures when live — the zero-cost-when-disabled
#     default is covered by every other leg running with them off;
#  4. the multi-chip dryrun: the full training step jit-compiled and
#     executed on an 8-device mesh (real dp/sp shardings);
#  5. the shardlint legs: source lint over heat_tpu/ (undeclared host
#     syncs, bare jax.jit, unsanitized public ops) and the IR check of
#     the __graft_entry__ training step on the 8-device CPU mesh
#     (ht.analysis.check: implicit reshards, replicated
#     materializations, missed donations). Warnings report only;
#     error-severity findings fail the leg.
set -euo pipefail
cd "$(dirname "$0")/.."

python -m pytest tests/ -q "$@"

XLA_FLAGS="--xla_force_host_platform_device_count=5" \
  python -m pytest tests/ -q -m "not slow" --ignore tests/test_multiprocess.py "$@"

HEAT_TPU_TELEMETRY=1 python -m pytest tests/test_smoke.py tests/test_observability.py -q "$@"

XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
  python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun_multichip(8): OK')"

# sort-kernel legs (ISSUE 4): the kernel family FORCED on CPU — the
# Pallas radix block kernel runs in interpret mode, the XLA radix and
# blocked-columnsort engines natively — against the lax.sort oracle
# (leg 8); and the HEAT_TPU_SORT_KERNEL=0 escape hatch over the public
# sort surface, proving the hatch is oracle-identical (leg 9)
HEAT_TPU_SORT_KERNEL=1 python -m pytest tests/test_kernels_sort.py -q "$@"

HEAT_TPU_SORT_KERNEL=0 python -m pytest tests/test_manipulations.py tests/test_kernels_sort.py -q -k "sort" "$@"

# relayout-kernel legs (ISSUE 5), mirroring the sort legs: the
# lane-packing pack/unpack FORCED onto the Pallas tiled-copy kernel
# (interpret mode on CPU) under the whole redistribution surface
# (leg 10); and the HEAT_TPU_RELAYOUT_KERNEL=0 escape hatch, proving
# the XLA formulation is bit-identical over the packed programs
# (leg 11)
HEAT_TPU_RELAYOUT_KERNEL=1 python -m pytest tests/test_kernels_relayout.py tests/test_redistribution.py -q "$@"

HEAT_TPU_RELAYOUT_KERNEL=0 python -m pytest tests/test_kernels_relayout.py -q "$@"

# overlap legs (ISSUE 6), mirroring the kernel legs: forced software
# pipelining + collective-matmul ring forms over the redistribution and
# linalg suites (Pallas-interpret compatible — the packed-pivot programs
# run their relayout kernels in interpret mode on CPU) (leg 12); and the
# HEAT_TPU_REDIST_OVERLAP=0 escape hatch, proving the sequential oracle
# is bit-identical over the same surface (leg 13). ISSUE 19 extends
# both legs over the dense-factorization suite: the ring schedules
# (polar / eigh / cholesky / lu / solve) must be bit-identical under
# pipelined and sequential issue order — the suite's pinned seq/pipe
# parity tests run under BOTH gate values.
HEAT_TPU_REDIST_OVERLAP=1 python -m pytest tests/test_overlap.py tests/test_redistribution.py tests/test_linalg.py tests/test_kernels_relayout.py tests/test_factorizations.py -q "$@"

HEAT_TPU_REDIST_OVERLAP=0 python -m pytest tests/test_overlap.py tests/test_redistribution.py tests/test_factorizations.py -q "$@"

# wire-quant legs (ISSUE 7), mirroring the overlap legs: the int8 wire
# codec FORCED on CPU over the redistribution + optim suites — the
# admissibility policy keeps every bit-exact contract exact while the
# big-spec programs compile (and the mid-size ones execute) with
# encoded payloads (leg 14); and the HEAT_TPU_WIRE_QUANT=0 escape
# hatch, proving the full-width plans/programs are byte-identical to
# the PR 6 forms (leg 15). (The codec is pure XLA — no Pallas path to
# interpret-gate. RingKernelAttention is excluded the way the PR-2
# notes document: those tests carry a container capability gate —
# head_dim multiples of 128 — that fails STANDALONE on this image with
# or without any quant gate; leg 1 covers them in the full suite.)
HEAT_TPU_WIRE_QUANT=1 python -m pytest tests/test_quant.py tests/test_redistribution.py tests/test_nn_optim.py -q -k "not RingKernelAttention" "$@"

HEAT_TPU_WIRE_QUANT=0 python -m pytest tests/test_quant.py tests/test_redistribution.py tests/test_overlap.py -q "$@"

# two-tier topology legs (ISSUE 8): the simulated 2x4 factorization of
# the 8-device mesh forced over the redistribution/overlap/quant suites
# — tiered plans execute end to end, census == tiered plan, the flat
# golden pins hold via their explicit topology="flat" anchors (leg 16);
# the two-tier dryrun pins hierarchical-vs-flat bit-identity, TSQR
# slice-major census, and the hierarchical DP wire (leg 17); and the
# auto-on-CPU no-op parity leg proves HEAT_TPU_TOPOLOGY=auto on a
# single-slice world dumps plans byte-identical to the unset default
# (leg 18)
HEAT_TPU_TOPOLOGY=2x4 python -m pytest tests/test_topology.py tests/test_redistribution.py tests/test_overlap.py tests/test_quant.py -q "$@"

XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu HEAT_TPU_TOPOLOGY=2x4 \
  python -c "import __graft_entry__ as g; g.dryrun_two_tier(8); print('dryrun_two_tier(8): OK')"

topo_a="$(mktemp)"; topo_b="$(mktemp)"
python scripts/redist_plans.py > "$topo_a"
HEAT_TPU_TOPOLOGY=auto python scripts/redist_plans.py > "$topo_b"
diff "$topo_a" "$topo_b"
echo "HEAT_TPU_TOPOLOGY=auto on CPU: flat plans byte-identical"
rm -f "$topo_a" "$topo_b"

# serving legs (ISSUE 9): (19) warmup export into a fresh store, then a
# FRESH process against the same store must serve every declared
# program from disk (--expect-hits: the cross-process cache-hit proof —
# an AOT-served cold start compiles 0 programs); (20) the dispatcher
# concurrency + AOT suite FORCED on (HEAT_TPU_SERVING_AOT=1 with a
# scratch store, so the ambient default-enabled hooks are exercised by
# every test, not just the ServingCase-anchored ones); (21) the
# HEAT_TPU_SERVING_AOT=0 escape hatch over the serving + jit suites —
# hooks never install and the wrapper runs its exact pre-serving paths
srv_store="$(mktemp -d)"
HEAT_TPU_SERVING_AOT=1 HEAT_TPU_SERVING_CACHE="$srv_store" python scripts/warmup.py > /dev/null
HEAT_TPU_SERVING_AOT=1 HEAT_TPU_SERVING_CACHE="$srv_store" python scripts/warmup.py --expect-hits
echo "serving warmup reload: cross-process AOT hits OK"

srv_scratch="$(mktemp -d)"
HEAT_TPU_SERVING_AOT=1 HEAT_TPU_SERVING_CACHE="$srv_scratch" \
  python -m pytest tests/test_serving.py -q "$@"
rm -rf "$srv_store" "$srv_scratch"

HEAT_TPU_SERVING_AOT=0 python -m pytest tests/test_serving.py tests/test_jit.py tests/test_jit_sweep.py -q "$@"

# out-of-core staging legs (ISSUE 11), mirroring the kernel legs:
# (22) HEAT_TPU_OOC=1 FORCES the staged window pipeline — every
# rank-budget hsvd sketch on the supported (single-device-orientation)
# path runs host->slab->compute windows — over the linalg + cluster +
# redistribution suites, which must stay green AND bit-identical to
# the in-HBM forms (tests/test_staging.py pins the sweep); (23) the
# HEAT_TPU_OOC=0 escape hatch: staging never engages, HostArray twins
# materialize, exact pre-staging program forms
HEAT_TPU_OOC=1 python -m pytest tests/test_staging.py tests/test_linalg.py tests/test_estimators.py tests/test_redistribution.py -q "$@"

HEAT_TPU_OOC=0 python -m pytest tests/test_staging.py tests/test_linalg.py -q "$@"

# resilience legs (ISSUE 13): (24) the chaos drill at the even AND odd
# meshes — a seeded slice kill mid-fit at the simulated 2x4 topology:
# detection is a typed WorldChangedError (never a hang), the live
# dispatcher's queued requests shed as
# ServingOverloaded(reason="resize") while its in-flight batch
# COMPLETES, the world re-resolves onto the survivors with the epoch
# bump + cache sweep, and the checkpoint-resumed fit is BIT-IDENTICAL
# to an uninterrupted same-seed run (a chaos-truncated newest envelope
# falls back to its committed predecessor); (25) the resilience +
# serving suites with the runtime FORCED on; (26) the
# HEAT_TPU_RESILIENCE=0 escape hatch: golden plan dumps byte-identical
# with the runtime off, and the suite's escape-hatch pins pass
XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
  HEAT_TPU_RESILIENCE=1 python scripts/chaos_drill.py
XLA_FLAGS="--xla_force_host_platform_device_count=5" JAX_PLATFORMS=cpu \
  HEAT_TPU_RESILIENCE=1 python scripts/chaos_drill.py

HEAT_TPU_RESILIENCE=1 python -m pytest tests/test_resilience.py tests/test_serving.py -q "$@"

res_a="$(mktemp)"; res_b="$(mktemp)"
python scripts/redist_plans.py > "$res_a"
HEAT_TPU_RESILIENCE=0 python scripts/redist_plans.py > "$res_b"
diff "$res_a" "$res_b"
HEAT_TPU_RESILIENCE=0 python -m pytest tests/test_resilience.py -q "$@"
echo "HEAT_TPU_RESILIENCE=0: golden dumps byte-identical + escape-hatch pins clean"
rm -f "$res_a" "$res_b"

# tracing legs (ISSUE 15): (27) span collection FORCED on
# (HEAT_TPU_TRACE=1) over the four instrumented layers — redistribution
# lap probes, staging window spans, the dispatcher lifecycle, and the
# resilience slab/drain spans — every suite must stay green with the
# recorder live (the census==plan pins in tests/test_tracing.py run
# anchored, the rest prove the probes never perturb behavior); (28) the
# HEAT_TPU_TRACE=0 escape hatch: the gate is registered
# affects_programs=False, so the golden plan dumps must be
# byte-identical with tracing hard-off vs forced on — the diff IS the
# proof that observation never changes what runs; (29) the
# metrics_dump/export_trace smoke: one workload process emits
# parseable Prometheus text, a telemetry JSON snapshot, and a
# Chrome-trace JSON doc that round-trips
HEAT_TPU_TRACE=1 python -m pytest tests/test_tracing.py tests/test_redistribution.py tests/test_staging.py tests/test_serving.py tests/test_resilience.py -q "$@"

trace_a="$(mktemp)"; trace_b="$(mktemp)"
HEAT_TPU_TRACE=0 python scripts/redist_plans.py > "$trace_a"
HEAT_TPU_TRACE=1 python scripts/redist_plans.py > "$trace_b"
diff "$trace_a" "$trace_b"
HEAT_TPU_TRACE=0 python -m pytest tests/test_tracing.py -q "$@"
echo "HEAT_TPU_TRACE=0: golden dumps byte-identical to =1 + zero-overhead pins clean"
rm -f "$trace_a" "$trace_b"

trace_json="$(mktemp)"
HEAT_TPU_TRACE=1 python scripts/metrics_dump.py --trace "$trace_json" | python -c "
import sys
lines = sys.stdin.read().splitlines()
assert any(l.startswith('# TYPE heat_tpu_') for l in lines), 'no TYPE comments'
vals = [l for l in lines if l and not l.startswith('#')]
assert vals, 'no samples rendered'
for l in vals:
    float(l.rpartition(' ')[2])
print(f'prometheus text: {len(vals)} samples OK')
"
HEAT_TPU_TRACE=1 python scripts/metrics_dump.py --json > /dev/null
python - "$trace_json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
evs = doc["traceEvents"]
assert evs and any(e["ph"] == "X" for e in evs), "no complete span events"
print(f"chrome trace: {len(evs)} events OK")
EOF
rm -f "$trace_json"

# the single CI lint entry (ISSUE 14; ISSUE 17 adds pass 6): passes
# 2 + 4 + 5 + 6 — srclint (SL2xx source hygiene), effectcheck (SL40x
# gate/cache-key staleness, raw gate reads, lock discipline, pipeline
# protocol, swallowed worker exceptions), commcheck (SL504 unfenced
# dispatch entries) and numcheck (SL602 planar precision policy:
# deleting the PR 5 precision="highest" default is an error here) — in
# ONE process, gated at error severity, with one SARIF document
# carrying one run per pass for CI annotations. Exit codes are pinned
# format-invariant (tests/test_analysis.py::TestLintCLI): 0 on the
# clean tree, 1 on any error-severity finding, text or sarif alike.
python scripts/lint.py heat_tpu/ --pass all
python scripts/lint.py heat_tpu/ --pass all --format sarif > /dev/null
echo "lint --pass all: SL2xx/SL4xx/SL5xx/SL6xx clean + SARIF emitted"

# seeded-bug proof (ISSUE 12 + 14 + 17 acceptance): each mutation
# removes ONE invariant — a gate from a program-cache key (SL402), a
# lock acquisition from a guarded dispatcher path (SL404), a pair from
# a ring_all_gather permutation (SL502), the full-axis reduction off a
# collective-launching cond predicate (SL501), the epoch-fence call
# off the executor / the serving endpoint (SL504), the planar
# precision="highest" default (SL602), the gram builders' f32
# accumulator (SL601), the f32 error-feedback carry (SL603), a golden
# plan's tolerance annotation / encode tags / wire markers (the
# tolerance invariant, step named) — and the lint must trip on the
# mutated source with the invariant named.
python -m pytest tests/test_effectcheck.py tests/test_commcheck.py tests/test_numcheck.py -q -k "mutation" "$@"

# pass-5 IR + progress legs (ISSUE 14): the SL5xx golden bad fixtures
# trip at their declared severities with clean twins, the shipped
# collective contracts pin commcheck-clean, every golden plan replays
# to completion under the progress invariant, and a hand-mutated dump
# fails scripts/verify_plans.py NAMING "progress" (the sweep test).
python -m pytest tests/test_commcheck.py -q "$@"

# pass-6 IR + tolerance legs (ISSUE 17): the SL6xx golden bad fixtures
# trip at their declared severities with clean twins, the shipped
# numeric contracts (TSQR, hSVD-L0, ring cmatmul, the quantized
# all-reduce, the kcluster endpoint, the training step) pin
# numcheck-clean, and every golden plan composes to exactly its
# quant.tol annotation under the tolerance invariant.
python -m pytest tests/test_numcheck.py -q "$@"

XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
  python scripts/lint.py --ir-entry 8

# golden-plan determinism + well-formedness: redistribution plans key
# the executor's program cache, so two fresh processes must serialize
# the golden matrix byte-identically (leg 7) — at the flat default AND
# at the forced 2x4/2x8 two-tier topologies (ISSUE 8: tier annotations
# fold into plan_ids, so the tiered dumps must be just as
# deterministic). ISSUE 10 adds the verify_plan sweep over every dumped
# plan (flat/2x4/2x8, quant on+off — redist_plans dumps both): byte
# identity catches nondeterminism, the verifier catches a plan that is
# deterministically MALFORMED (broken composition/conservation/codec
# pairing/tier labels/overlap structure/plan-id) and fails the leg with
# the violated invariant named. ISSUE 11 adds the staged golden plans
# (host-staging window schedules) to every dump: the staging invariant
# (stage pairing, window conservation, depth-2 slab occupancy, lattice
# time model) is proven on each. ISSUE 14 adds the progress invariant
# to the same sweep: a symbolic per-device replay proving every
# participant runs each plan to completion — congruent subgroup
# structure, rings closing in exactly p-1 hops, hierarchical ici/dcn
# lap pairs sharing one chunk, depth-2 lap tags issued in consume
# order — so a dump that would HANG a mesh fails here, not on TPU
plans_a="$(mktemp)"; plans_b="$(mktemp)"
python scripts/redist_plans.py > "$plans_a"
python scripts/redist_plans.py > "$plans_b"
diff "$plans_a" "$plans_b"
python scripts/verify_plans.py "$plans_a"
echo "redist golden plans: deterministic + well-formed ($(wc -l < "$plans_a") plans)"
for topo in 2x4 2x8; do
  python scripts/redist_plans.py --topology "$topo" > "$plans_a"
  python scripts/redist_plans.py --topology "$topo" > "$plans_b"
  diff "$plans_a" "$plans_b"
  python scripts/verify_plans.py --topology "$topo" "$plans_a"
  echo "redist golden plans @$topo: deterministic + well-formed ($(wc -l < "$plans_a") plans)"
done
rm -f "$plans_a" "$plans_b"

# tolerance-budget sweep (ISSUE 17): the standalone check_tolerance
# entry re-proves the pass-6 dynamic invariant over every dumped golden
# plan (flat + both tiered topologies) — each plan's end-to-end error
# bound, recomposed from its recorded per-step tolerances, equals the
# schedule-level quant.tol annotation — and a hand-malformed tol
# annotation fails NAMING the tolerance invariant (verify_plans.py
# gates the same defect; this leg pins the findings-collecting face).
tol_dump="$(mktemp)"
python scripts/redist_plans.py > "$tol_dump"
python scripts/redist_plans.py --topology 2x4 >> "$tol_dump"
python scripts/redist_plans.py --topology 2x8 >> "$tol_dump"
python - "$tol_dump" <<'EOF'
import json, sys
from heat_tpu.analysis.planverify import check_tolerance
n = nq = 0
mutable = None
for line in open(sys.argv[1]):
    name, _, payload = line.strip().partition("\t")
    if not payload:
        continue
    findings = check_tolerance(payload)
    assert not findings, f"{name}: {[str(f) for f in findings]}"
    n += 1
    d = json.loads(payload)
    if d.get("quant"):
        nq += 1
        mutable = mutable or d
assert n and nq, f"swept {n} plans but {nq} quantized"
mutable["quant"]["tol"] = float(mutable["quant"]["tol"]) * 2
bad = check_tolerance(mutable)
assert bad and all(f.rule == "SL605" for f in bad), [str(f) for f in bad]
assert "tol" in str(bad[0]), str(bad[0])
print(f"check_tolerance: {n} plan(s) ({nq} quantized) compose to their "
      "declared budgets; malformed tol names SL605")
EOF
rm -f "$tol_dump"

# spmm-kernel legs (ISSUE 18), mirroring the sort/relayout legs: the
# brick SpMM/SDDMM family FORCED onto the Pallas scalar-prefetch
# kernels (interpret mode on CPU) over the sparse + graph suites —
# every workload from raw brick matmuls through the PageRank fixpoint
# and spectral embedding runs kernel-backed against the same oracles;
# and the HEAT_TPU_SPMM_KERNEL=0 escape hatch over the same surface,
# proving the gather-free XLA formulation is bit-identical. (The
# 5-device odd-mesh leg above already replays the sparse suite: it
# runs all of tests/, which includes test_spmm.py/test_graph.py/
# test_sparse.py since this ISSUE.)
HEAT_TPU_SPMM_KERNEL=1 python -m pytest tests/test_spmm.py tests/test_sparse.py tests/test_graph.py -q "$@"

HEAT_TPU_SPMM_KERNEL=0 python -m pytest tests/test_spmm.py tests/test_sparse.py tests/test_graph.py -q "$@"

