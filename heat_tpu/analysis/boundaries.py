"""Declared host boundaries — the whitelist the host-sync rules check.

The repo invariant (rule SL201) is: device values never round-trip
through the host inside library code, because one ``jax.device_get``
serializes the dispatch pipeline and, in a multi-host world, reads only
the addressable shards. Every legitimate sync must therefore be
DECLARED here, in one reviewable file, in the category that states
*why* it is allowed:

- :data:`HOST_MODULES` — whole modules whose contract IS host transfer
  (file I/O). Everything in them is exempt.
- :data:`HOST_FUNCS` — functions whose API contract is to produce or
  ingest a HOST value (``.numpy()`` export, ``__repr__``, host complex
  assembly). Calling them eagerly is the point; they are unreachable
  from traced code by construction (tracing them raises).
- :data:`DATA_DEPENDENT_BOUNDARIES` — eager-only ops whose OUTPUT SHAPE
  depends on data (``unique``/``nonzero`` counts, hSVD adaptive rank).
  The host read is what makes the result shape concrete; these ops are
  documented as untraceable (core/jit.py limitation #1).
- :data:`HOST_BOUNDARIES` — the narrow category: a deliberate host
  round-trip inside an otherwise traceable compute path. Each entry is
  NAMED so tests can pin the exact population; tier-1 asserts the only
  ``core/`` entry is ``percentile-q``. Adding a sync to a compute path
  means adding a named entry here — the diff is the declaration.

Matching is by (posix path suffix, dotted enclosing-scope qualname);
line numbers are deliberately not part of a declaration so unrelated
edits to a file do not invalidate it.
"""

from __future__ import annotations

import functools
import re

from typing import Dict, Optional, Tuple

__all__ = [
    "HOST_MODULES",
    "HOST_FUNCS",
    "DATA_DEPENDENT_BOUNDARIES",
    "HOST_BOUNDARIES",
    "PLANNER_MODULES",
    "RING_SCHEDULE_MODULES",
    "WIRE_CODEC_MARKER",
    "is_declared_sync",
    "planned_reshard_plan_id",
    "ring_schedule_module",
    "wire_codec_stamped",
]

# modules that are host I/O by contract (posix path suffixes)
HOST_MODULES: Tuple[str, ...] = (
    "core/io.py",       # save/load: hyperslab writes are host-side by nature
    "core/printing.py", # __str__ formatting renders on the host
    # checkpointing IS host I/O: durable state must cross to the host
    # to reach the persistent store (slab-streamed, ISSUE 13)
    "resilience/checkpoint.py",
)

# (path suffix, qualname) -> reason. Host-value producers/ingesters.
HOST_FUNCS: Dict[Tuple[str, str], str] = {
    ("core/dndarray.py", "DNDarray.__host_logical"): (
        "the single funnel behind .numpy()/.item()/float(): its contract "
        "is a host copy of the logical array"
    ),
    ("core/complex_planar.py", "host_complex"): (
        "assembles a host numpy complex array from the device plane pair "
        "(the planar analog of DNDarray.__host_logical)"
    ),
    ("core/complex_planar.py", "array_factory"): (
        "ingestion: normalizes arbitrary host/device input to planes at "
        "array-construction time (eager by definition)"
    ),
    ("sparse/dcsr_matrix.py", "DCSR_matrix.counts_displs_nnz"): (
        "exports the per-device nnz partition as host ints (metadata "
        "export API, the analog of the reference's counts/displs query)"
    ),
    ("sparse/dcsr_matrix.py", "DCSR_matrix.__repr__"): (
        "debug rendering of the CSR triple on the host"
    ),
    ("sparse/dbcsr_matrix.py", "DBCSR_matrix._to_scipy_bsr"): (
        "export: reassembles the global scipy BSR on the host (the "
        "brick analog of DNDarray.__host_logical — every .to_scipy()/"
        "oracle comparison funnels through it)"
    ),
    ("sparse/dbcsr_matrix.py", "sparse_dbcsr_matrix"): (
        "ingestion factory: normalizes arbitrary host/device/DCSR input "
        "to slab-laid bricks at construction time (the sparse analog of "
        "complex_planar.array_factory — eager by definition)"
    ),
    ("graph/pagerank.py", "_adjacency_to_scipy"): (
        "ingestion: normalizes any adjacency form (DBCSR/DCSR/DNDarray/"
        "host) to a host scipy CSR once at solve setup — the graph "
        "solvers build their brick operator from the host copy"
    ),
    ("preprocessing/sparse_encoders.py", "TfidfTransformer._counts_csr"): (
        "ingestion: normalizes fit() input to a host scipy CSR of term "
        "counts — document-frequency statistics are host-side by "
        "contract (fit is the eager estimation phase)"
    ),
    ("preprocessing/sparse_encoders.py", "OneHotEncoder.stream_transform"): (
        "slab-streamed transform whose contract is a HOST result: each "
        "window's encoded block is written back into the host output "
        "buffer (stage_out of the staging schedule it proves first)"
    ),
    ("preprocessing/sparse_encoders.py", "TfidfTransformer.stream_transform"): (
        "slab-streamed transform whose contract is a HOST result: the "
        "reweighted window lands in the host output buffer (stage_out "
        "of the proven staging schedule)"
    ),
    ("core/linalg/factorizations.py", "_solve_host_rhs"): (
        "staged solve against a host-resident RHS panel whose contract "
        "is a HOST result (ISSUE 19): each column window's solution is "
        "written back into the host output buffer (stage_out of the "
        "staging schedule it registers — the stream_transform pattern)"
    ),
    ("core/linalg/svd.py", "_svd_host"): (
        "staged values-only svd of a host-resident operand whose "
        "contract is a HOST-derived result (ISSUE 19): the Gram-pass "
        "singular values cross to the host once at the end of the "
        "stream (O(n) scalars against the O(mn) windowed operand)"
    ),
}

# (path suffix, qualname) -> reason. Eager-only data-dependent-shape ops.
DATA_DEPENDENT_BOUNDARIES: Dict[Tuple[str, str], str] = {
    ("core/parallel.py", "_host_counts"): (
        "unique/nonzero/compaction need the GLOBAL selected count on the "
        "host to size their output arrays — the documented eager-only "
        "boundary for data-dependent shapes"
    ),
    ("core/parallel.py", "distributed_unique"): (
        "the merged-unique total sizes the result; shape is data"
    ),
    ("core/parallel.py", "distributed_unique_rows"): (
        "the merged rows-unique total sizes the result; shape is data "
        "(the axis-mode twin of distributed_unique — ISSUE 11 satellite)"
    ),
    ("core/linalg/svdtools.py", "_hsvd_impl"): (
        "adaptive-rank hSVD reads the singular values to choose the rank "
        "the next merge level keeps — the rank IS data-dependent output "
        "shape (reference svdtools.py truncates on the host identically)"
    ),
    ("core/linalg/factorizations.py", "_projector_rank"): (
        "spectral divide-and-conquer eigh reads the projector trace to "
        "size the two subspace bases — the split rank IS data-dependent "
        "output shape (ISSUE 19; same category as hSVD's adaptive rank)"
    ),
}

# name -> (path suffix, qualname, reason). The NAMED whitelist: deliberate
# syncs inside otherwise traceable compute paths. Keep this list short —
# tier-1 pins its exact core/ population.
HOST_BOUNDARIES: Dict[str, Tuple[str, str, str]] = {
    "percentile-q": (
        "core/statistics.py",
        "percentile",
        "q is read to the host ONCE so the two bracketing ranks per "
        "percentile are static (they shape the program: two cross-shard "
        "row fetches instead of a gather); a traced q is rejected with a "
        "TypeError before this read",
    ),
    "optimizer-checkpoint-export": (
        "optim/dp_optimizer.py",
        "DataParallelOptimizer.checkpoint_state",
        "checkpoint export IS host transfer by contract (ISSUE 13): the "
        "base PRNG key crosses to the host so the resilience envelope "
        "can persist it; the array leaves stream through the checkpoint "
        "module's own slab writers (a declared host module)",
    ),
    "optimizer-checkpoint-restore": (
        "optim/dp_optimizer.py",
        "DataParallelOptimizer.load_checkpoint_state",
        "checkpoint restore's world-resize fold: the restored EF carry "
        "is folded row-wise on the host (r -> r % p_new, sum-preserving) "
        "before re-sharding onto the survivors — an eager, "
        "recovery-path-only transfer",
    ),
    "resilience-state-validate": (
        "resilience/elastic.py",
        "_finite_state",
        "the poisoned-collective detector of the elastic streaming loop "
        "(ISSUE 13): after each window update the (k, d) centers — a "
        "scalar-class array — are read to the host and checked finite; "
        "the read IS the detection, and it only runs when the elastic "
        "runtime is engaged (a ckpt/watcher/chaos hook was handed in), "
        "never on the default or HEAT_TPU_RESILIENCE=0 paths",
    ),
    "pagerank-stream-fixpoint": (
        "graph/pagerank.py",
        "pagerank_stream",
        "the streamed power iteration keeps the rank vector "
        "HOST-resident between slab-window sweeps (the edge list never "
        "fits on device — that is the point of the streamed form): one "
        "(n,)-vector readback per sweep funds the exact dangling-mass "
        "correction and the full-vector l1 convergence test; edge slabs "
        "themselves never round-trip",
    ),
    "spectral-ritz-extract": (
        "graph/spectral.py",
        "spectral_embedding",
        "Ritz extraction: the (m,) Lanczos alpha/beta coefficients are "
        "read to the host ONCE to assemble and eigh the m-by-m "
        "tridiagonal — an O(m^2) host solve against the O(n*m) device "
        "sweep; only scalar-class vectors cross, the Krylov basis stays "
        "on device for the final V @ W",
    ),
}


# ---------------------------------------------------------------------- #
# planner-issued reshards (rules SL101/SL102)                             #
# ---------------------------------------------------------------------- #
# Modules whose WHOLE PURPOSE is to launch resharding collectives: the
# redistribution executor compiles the planner's schedules (including
# the software-pipelined chunk loops and ppermute rings of ISSUE 6), and
# the collective-matmul kernels decompose the linalg all-gathers /
# reductions into ppermute chains consumed block-by-block — in both, the
# all-to-alls/all-gathers/collective-permutes ARE the budgeted,
# cost-modeled movement itself, not an accident of operand layout. The
# IR lint must not flag the subsystems' own programs as implicit
# reshards — it reports them at info severity with the stamp attached
# instead.
PLANNER_MODULES: Tuple[str, ...] = (
    "redistribution/executor.py",
    "kernels/cmatmul.py",
)

# every executor program runs under jax.named_scope("redist_plan_<id>")
# (12 hex chars: the Schedule.plan_id sha1 prefix) and every
# collective-matmul ring under jax.named_scope("cmatmul_ring_<tag>"), so
# the stamp lands in the HLO op_name metadata of each collective the
# program launches — the markers the IR lint keys on
_PLAN_MARKER = re.compile(r"redist_plan_([0-9a-f]{12})")
_CMATMUL_MARKER = re.compile(r"cmatmul_ring_([0-9a-z_]+)")


def planned_reshard_plan_id(hlo_line: str) -> Optional[str]:
    """The plan stamp on an HLO instruction line — a redistribution
    ``plan_id`` or a ``cmatmul:<tag>`` collective-matmul marker — or
    ``None`` when the collective is not planner-issued. ``ircheck`` uses
    this to downgrade SL101/SL102 findings on stamped programs to info
    severity (with the stamp attached) instead of flagging the
    subsystems' own schedules. An UNSTAMPED hand-rolled ppermute loop
    carries no marker and trips the rule at full severity (golden
    bad-fixture in ``tests/analysis_fixtures.py``)."""
    m = _PLAN_MARKER.search(hlo_line)
    if m:
        return m.group(1)
    m = _CMATMUL_MARKER.search(hlo_line)
    return f"cmatmul:{m.group(1)}" if m else None


# The wire codec (kernels/quant.py) wraps every encode/decode body in
# jax.named_scope("wire_codec_<mode>"); the stamp rides each traced
# eqn's name_stack the same way the executor's redist_plan scopes ride
# the HLO op_name. SL104's narrowing arm keys on it: a STAMPED
# float->int8 convert before a collective is the sanctioned
# block-quantized payload (info), an unstamped one is the
# gradient-compression accident the rule exists for (error —
# golden bad-fixture ``tests/analysis_fixtures.int8_wire_program``).
WIRE_CODEC_MARKER = "wire_codec_"


def wire_codec_stamped(name_stack: str) -> bool:
    """Does a traced eqn's name_stack carry the wire-codec stamp?"""
    return WIRE_CODEC_MARKER in name_stack


# Modules whose ppermute chains are DOCUMENTED ring schedules — the
# algorithm, not a relayout accident: the distributed sort networks and
# stencil/halo exchanges (core/parallel.py), the convolution halo
# exchange (core/signal.py), and ring attention's K/V rotation
# (nn/attention.py). SL101's collective-permute arm reports their hops
# at info severity, keyed on the source file of the instruction's stack frame
# (these bodies run under shard_map, not a stampable named scope); a
# hand-rolled ppermute loop anywhere else still trips the rule at full
# severity. (The other two library ppermute sites —
# redistribution/executor.py and kernels/cmatmul.py — stamp named
# scopes instead, see PLANNER_MODULES.)
RING_SCHEDULE_MODULES: Tuple[str, ...] = (
    "heat_tpu/core/parallel.py",
    "heat_tpu/core/signal.py",
    "heat_tpu/nn/attention.py",
)

_STACK_FRAME = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW = re.compile(r"^(\d+) (.*)$")


@functools.lru_cache(maxsize=8)
def _frame_files(module_text: str) -> Dict[int, str]:
    """``stack_frame_id`` -> source file of that frame, read from the
    ``FileNames`` / ``FileLocations`` / ``StackFrames`` tables at the
    head of a compiled module's text (instructions carry only the id)."""
    tables: Dict[str, Dict[int, str]] = {}
    current = None
    for line in module_text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            current = tables.setdefault(line, {})
        elif current is not None:
            row = _TABLE_ROW.match(line)
            if row:
                current[int(row.group(1))] = row.group(2)
            elif line.strip():
                break  # past the tables: the computations begin
            else:
                current = None

    def ref(row: str, field: str) -> int:
        m = re.search(field + r"=(\d+)", row)
        return int(m.group(1)) if m else 0

    out = {}
    for frame, row in tables.get("StackFrames", {}).items():
        loc = tables.get("FileLocations", {}).get(ref(row, "file_location_id"), "")
        name = tables.get("FileNames", {}).get(ref(loc, "file_name_id"), "")
        out[frame] = _norm(name.strip('"'))
    return out


def ring_schedule_module(hlo_line: str, module_text: str) -> Optional[str]:
    """The blessed ring-schedule module a collective-permute instruction
    of ``module_text`` was traced from (the source file of its
    ``stack_frame_id`` ends with an entry of
    :data:`RING_SCHEDULE_MODULES`), or ``None``."""
    m = _STACK_FRAME.search(hlo_line)
    if not m:
        return None
    path = _frame_files(module_text).get(int(m.group(1)), "")
    for suffix in RING_SCHEDULE_MODULES:
        if path.endswith(suffix):
            return suffix
    return None


def _norm(path: str) -> str:
    return path.replace("\\", "/")


def is_declared_sync(path: str, qualname: str) -> Tuple[bool, str]:
    """Is a host sync at (file, enclosing scope) declared?

    Returns ``(declared, category-or-name)``. ``qualname`` is the dotted
    enclosing-scope chain (``Class.method``, ``outer.inner``); a
    declaration for ``outer`` covers syncs in its nested functions (the
    boundary owns its helpers).
    """
    p = _norm(path)
    for suffix in HOST_MODULES:
        if p.endswith(suffix):
            return True, f"host-module:{suffix}"
    parts = qualname.split(".") if qualname else []
    prefixes = {".".join(parts[: i + 1]) for i in range(len(parts))}

    def _match(decls):
        for (suffix, qn), _reason in decls.items():
            if p.endswith(suffix) and (qn == qualname or qn in prefixes):
                return qn
        return None

    qn = _match(HOST_FUNCS)
    if qn:
        return True, f"host-func:{qn}"
    qn = _match(DATA_DEPENDENT_BOUNDARIES)
    if qn:
        return True, f"data-dependent:{qn}"
    for name, (suffix, qn, _reason) in HOST_BOUNDARIES.items():
        if p.endswith(suffix) and (qn == qualname or qn in prefixes):
            return True, name
    return False, ""
