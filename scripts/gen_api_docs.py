"""Generate docs/API.md — the flat API index (the analog of the
reference's sphinx autosummary site, doc/source/autoapi). Walks the
public packages, lists every ``__all__`` export with its signature and
first docstring line. Regenerate after adding exports:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python scripts/gen_api_docs.py
"""

from __future__ import annotations

import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import heat_tpu as ht  # noqa: E402

SECTIONS = [
    ("heat_tpu (core array API)", ht, "ht"),
    ("heat_tpu.linalg", ht.linalg, "ht.linalg"),
    ("heat_tpu.random", ht.random, "ht.random"),
    ("heat_tpu.sparse", ht.sparse, "ht.sparse"),
    ("heat_tpu.cluster", ht.cluster, "ht.cluster"),
    ("heat_tpu.classification", ht.classification, "ht.classification"),
    ("heat_tpu.naive_bayes", ht.naive_bayes, "ht.naive_bayes"),
    ("heat_tpu.regression", ht.regression, "ht.regression"),
    ("heat_tpu.spatial", ht.spatial, "ht.spatial"),
    ("heat_tpu.graph", ht.graph, "ht.graph"),
    ("heat_tpu.preprocessing", ht.preprocessing, "ht.preprocessing"),
    ("heat_tpu.nn", ht.nn, "ht.nn"),
    ("heat_tpu.kernels (single-chip kernels)", ht.kernels, "ht.kernels"),
    ("heat_tpu.analysis (static shardlint)", ht.analysis, "ht.analysis"),
    ("heat_tpu.analysis.effectcheck (pass 4: gatecheck + racecheck)", ht.analysis.effectcheck, "ht.analysis.effectcheck"),
    ("heat_tpu.core.gates (env-gate registry)", ht.core.gates, "ht.core.gates"),
    ("heat_tpu.redistribution (resplit/reshape planner)", ht.redistribution, "ht.redistribution"),
    ("heat_tpu.serving (AOT cache + dispatcher)", ht.serving, "ht.serving"),
    ("heat_tpu.resilience (elastic fault-tolerant runtime)", ht.resilience, "ht.resilience"),
    ("heat_tpu.observability (collective inspector)", ht.observability, "ht.observability"),
    ("heat_tpu.telemetry (metrics registry; = observability.telemetry)", ht.telemetry, "ht.telemetry"),
    ("heat_tpu.tracing (span tracer + flight recorder; = observability.tracing)", ht.tracing, "ht.tracing"),
    ("heat_tpu.optim", ht.optim, "ht.optim"),
    ("heat_tpu.utils.data", ht.utils.data, "ht.utils.data"),
    ("heat_tpu.utils (checkpoint / monitor)", ht.utils, "ht.utils"),
]


# hand-written notes under a section's heading: what no signature shows
NOTES = {
    "heat_tpu.observability (collective inspector)": """**Spans on the profiler's clock.** `with ht.utils.monitor.trace(path):` (or any
`jax.profiler.start_trace`) captures heat_tpu's own spans beside the device ops, on one clock,
with nothing to enable: every `ht.tracing.span` is a `jax.profiler.TraceAnnotation`. Open the
trace in Perfetto or TensorBoard: the spans are on the thread lines of `/host:CPU`, the device
ops on `/device:TPU:<i>`. With `HEAT_TPU_TRACE=1` (or telemetry on) the same spans are also
kept, with parent ids, in the ring that `ht.observability.export_trace` writes. The readers in
`benchmarks/layers/` are named in root `PERF.md` section 3.

| span | around | read by |
|---|---|---|
| `ht.call.hsvd_rank`, `ht.call.hsvd_rtol`, `ht.call.hsvd` | the whole public call | `host_wrapper_ms_per_call` (self time) |
| `ht.call.hsvd.prepare` | `sanitize_in`, checks, dtype, `astype`, the sketch-size arithmetic; on the staged split path also the orientation (`phys.T`) | `host_wrapper_ms_per_call` |
| `ht.call.hsvd.level0` | lookup and call of the level-0 program (the program spans nest in it). The rank-budget call on a split array is ONE program (`hsvd.dist_rank`: level 0, merge, both factors, the estimate), launched here | `host_wrapper_ms_per_call` |
| `ht.call.hsvd.merge` | staged split path and tolerance mode: `_merge_svd` and the rank/err arithmetic after it, any `device_get` | `host_wrapper_ms_per_call` |
| `ht.call.hsvd.wrap` | `_err_scalar`, the `DNDarray` constructions, sigma's placement | `host_wrapper_ms_per_call` |
| `ht.call.hsvd.postprocess` | `_postprocess_v` (the complementary factor by a third pass over `A`; staged split path only) | `host_wrapper_ms_per_call` |

Inside the `hsvd.dist_rank` program the device ops carry `jax.named_scope`s, so a device trace
tells the phases of the one program apart: `hsvd.level0` (each device's two streams over its block
and the local factors) and `hsvd.merge` (the exchange of the stacked factor, its factorization, `U`
from the local factors, the estimate). They are in every op's `op_name` metadata.

| span | around | read by |
|---|---|---|
| `ht.call.kmeans.fit`, `ht.call.kmeans.predict` | `KMeans.fit` (the fused fit), `predict` | `host_wrapper_ms_per_call` |
| `ht.call.kmeans.init`, `.program`, `.wrap` | initial centres or seed key; lookup of the step and the fused program and the call; placement and the two `DNDarray`s (shared by `KMedians`/`KMedoids.fit`) | `host_wrapper_ms_per_call` |
| `ht.call.kmedians.fit`, `ht.call.kmedoids.fit` | `KMedians.fit`, `KMedoids.fit` (the fused fit: the three spans above nest in it) | `host_wrapper_ms_per_call` |
| `ht.call.qr` | the whole public `ht.linalg.qr` call (since PR 34) | `host_wrapper_ms_per_call` (self time) |
| `ht.call.qr.prepare`, `.wrap` | `sanitize_in`, checks, dtype, `astype`, which path; the `DNDarray`s of `Q` and `R` and their placement. Between them the lookup and call of the program (`qr.local` on one device or a replicated array, `qr.tsqr` on a split one): the program spans nest in `ht.call.qr` itself | `host_wrapper_ms_per_call` |
| `ht.call.percentile` | the whole public `ht.percentile` / `ht.median` call (since PR 38; `RobustScaler.fit`'s one call nests in `ht.call.robustscaler.fit`) | `host_wrapper_ms_per_call` (self time) |
| `ht.call.percentile.prepare`, `.wrap` | `sanitize_in`, `q` to the host (an `ht.sync.read` where it is a device value), which form (`_selection_form`); where the counting selection serves, the `DNDarray` of the result. Between them the lookup and call of the one program (`percentile.select`): the program spans nest in `ht.call.percentile` itself. A call that sorts has no `.wrap` | `host_wrapper_ms_per_call` |
| `ht.call.robustscaler.fit`, `.transform`, `.inverse_transform` | `RobustScaler.fit` (one `percentile` call and the small program `scaler.robust_stats`), `transform` and `inverse_transform` (one program each, `scaler.transform`, and the result's placement) | `host_wrapper_ms_per_call` |
| `ht.call.robustscaler.fit_transform` | the whole public `RobustScaler.fit_transform` call (since PR 39). Where the fit's percentiles come from the counting selection (`core.statistics._form_of`, the test `ht.percentile` itself makes: 2-D, axis 0, not planar, then `_selection_form` says `"pallas"` or `"xla"`) and a flag is set: the reckoning of the key on the host, the lookup and call of ONE program (`scaler.robust_fit_transform`: the selection, the two statistics and the transform; no `ht.call.percentile`, no `.fit`, no `.transform` inside it) and the result's placement. Everywhere else (`"sort"`; both flags off) the staged form: `ht.call.robustscaler.fit` and `.transform` nest in it | `host_wrapper_ms_per_call` |
| `ht.op.binary`, `ht.op.unary`, `ht.op.reduce`, `ht.op.cum`, `ht.op.matmul`, `ht.op.transpose` | one eager op: lookup, call and wrapping | `host_wrapper_ms_per_call` |
| `ht.program.hit` | entered right after a lookup that a builder's `lru_cache` served (`cache=` names the builder) | `host_launch_ms_per_call` |
| `ht.program.miss` | a lookup that built; the builder's time | `program_cache_misses`, `host_launch_ms_per_call` |
| `ht.program.launch` | every call of a built program: the host side of the jitted call, argument handling to enqueue | `launches_per_call`, `host_prelaunch_ms_per_call`, `host_launch_ms_per_call` |
| `ht.program.compile` | the first call after a miss (trace + compile) | as `launch` |
| `ht.comm.place`, `ht.comm.shard`, `ht.comm.reshard` | `communication.place`, `MeshCommunication.shard`, `reshard_phys` | `host_comm_ms_per_call` |
| `ht.sync.read` | wherever the library itself brings a device value to the host (since PR 36; `what=` says which): `DNDarray.numpy` / `__array__` / `tolist` / `item` / `float()` and the other scalar casts (one span, in the shared gather), the first read of an estimator's `n_iter_` / `inertia_` (the cached value enters none), the spectrum of hSVD's tolerance and staged modes, `percentile`'s `q`, `unique`'s counts, the eigensolver's projector rank, a staged solve's windows, `save`'s slabs, `print`, a tile | `sync_read_ms_per_call` |
| `ht.sync.wait` | wherever the library itself waits for a program without reading it: the sort and spmm autotuners' timed runs, the dispatcher's fence | `sync_read_ms_per_call` |

**The calling thread's counters** (since PR 36). The outermost span of a public call (`ht.call.hsvd_rank`,
`ht.call.hsvd_rtol`, `ht.call.hsvd`, `ht.call.qr`, `ht.call.kmeans.fit`, `ht.call.kmedians.fit`,
`ht.call.kmedoids.fit`, `ht.call.kmeans.predict`, `ht.call.percentile`, `ht.call.robustscaler.fit`,
`.transform`, `.inverse_transform`, `.fit_transform`) is entered through `ht.tracing.call_span`. Under a live
profiler session, and only then (without one it costs the `is_enabled()` read every span pays), it reads two
CPU clocks at entry and passes them as the annotation's arguments; the profiler keeps them as the event's
integer stats (`ProfileData` event `.stats`; Perfetto shows them as the slice's arguments). Nothing is
computed in the program: the difference between two consecutive calls is one cycle (the call, the caller's
wait, the caller's loop).

| argument | from | what its difference over a cycle says | read by |
|---|---|---|---|
| `thread_cpu_ns` | `time.thread_time_ns()` | CPU time of the calling thread: against the cycle's wall less the device time, whether the thread computed or slept | `host_thread_cpu_ms_per_call` |
| `process_cpu_ns` | `time.process_time_ns()` | CPU time of all threads: a runtime that polls for completion shows the cycle's wall here, one that sleeps shows little | `host_process_cpu_ms_per_call` |

The starts of the same spans give the cycles, and `late_call_ms_in_window` the time of those far over
the median. The chip machines run gVisor (`uname`: `runsc`, Linux 4.4.0; my chip run, PR 36): its CPU clocks
tick in 10 ms, so a window of n cycles resolves a mean to 10 / n ms and no finer, and the count of ticks in
a window is itself a sample (1 to 9 over twelve windows of one state): a level is a mean over runs, and a
window that resolves no finer than 0.5 ms a call (under twenty cycles) reads nothing (the two metrics list
the cells whose windows do). A read is a system call of 5 us there, a traced entry 15 us in all. (PR 36
also read the thread's context switches and major faults from `getrusage(RUSAGE_THREAD)` and Python's
collections. gVisor counts no switch and no fault, so a zero there ruled nothing out, and no collection ran
in 1100 cycles: all were taken out again, and with them a metric of preemptions; a source that works under
gVisor is a later issue's.) For an operator, on a trace of the benchmark (`bench.call` / `bench.wait` say
where a call starts and its wait ends; `run.py` removes its own once it is read, so a wrapper that copies
`benchmarks/.trace` first keeps one):

    python benchmarks/hostside.py <file.xplane.pb> [--census]

prints one line a call (the cycle's wall, the wall of `bench.call` and `bench.wait`, the time under
`ht.sync.*`, the two differences; `late` marks a cycle over 1.25 x the median) and, with `--census`, the
runtime's own host events inside `ht.program.launch` and inside `bench.wait`, by name and thread.

Counters behind the telemetry switch (`ht.telemetry.enable()`): `<builder>.hit`, `.miss`,
`.build`, `.compile` for every observed builder (`op.binary`, `op.unary`, `op.reduce`, `op.cum`,
`hsvd.sketched_rank`, `hsvd.one_view_rank`, `hsvd.sketched`, `hsvd.local_svd`, `hsvd.dist_rank`,
`hsvd.staged_rank_tail`, `hsvd.staged_oneview_tail`, `qr.tsqr`, `qr.local`, `kmeans.lloyd_step`,
`kmeans.partial_fit_step`, `kcluster.fused_fit`, `kcluster.predict`, `percentile.select`,
`scaler.robust_stats`, `scaler.transform`, `scaler.robust_fit_transform`), `ht.jit.cache.hit`/`.miss`,
`comm.shard.calls`/`.bytes`, `comm.reshard.calls`/`.bytes`, and `hsvd.pass2.one_dot` /
`hsvd.pass2.tiled` (which form of the two-pass sketch's second pass a program was built with: one
dot that reads f32 `A` once, where pass 1 was the Pallas kernel, or the tiled loop `_pass2_tiles`
everywhere else; counted once per built program, like a `.miss`), and, once per call of an hSVD on a
split array, `hsvd.dist.merge.gather` / `hsvd.dist.merge.tsqr` (which merge ran: the stacked factor
gathered whole and factored on every device, up to 128 columns, or TSQR over its rows) and
`hsvd.dist.u.local` / `hsvd.dist.u.postprocess` (where the split-side factor came from: the devices'
own level-0 factors times their rows of the merge's `Z`, or `_postprocess_v`'s third pass over `A`),
and, once per `KMeans.fit` (since PR 28), `kmeans.step.fused` / `kmeans.step.xla` (which Lloyd step
the fit's program runs, as `cluster._pallas.lloyd_pass_serves` decided from backend, dtype, shape
and split: the Pallas pass that reads f32 `X` once an iteration, or XLA's two streams):
for an operator's `ht.telemetry.report()`, read by no benchmark metric. On the device the pass is
named `kmeans_lloyd_pass` (the kernel) under `jax.named_scope("kmeans.lloyd_pass")`: a trace's op
line and the ledger's `breakdown.device_ops` show it by that name.

`ht.linalg.qr` (since PR 34): once per call on a tall real array the counter `qr.local.gram` /
`qr.local.householder` says which form of the local factorization the call's program has
(`core.linalg.qr._gram_serves`, from backend, dtype and shape: on a TPU, f32 or f64, from twice as many
rows as columns, a Cholesky-QR with a second pass whose tall work is matrix products; elsewhere XLA's
Householder QR), on one device (`qr.local`) and as level 0 of TSQR (`qr.tsqr`) alike. Whether the Gram
form's repair steps ran (ill-conditioned or rank-deficient input) is decided on the device and shows in
a device trace only: the ops of the program lie under `jax.named_scope`s `qr.tall.gram`,
`qr.tall.apply`, `qr.tall.finish` (products over row blocks of `A` and `Q`) and `qr.small.factor`
(Cholesky factors, triangular inverses, products of `R` factors), a repair step's under
`qr.tall.repair` / `qr.small.repair`, which a run on well-conditioned data never executes. The
benchmark's readers `qr_tall_ms_per_call` and `qr_small_ms_per_call` tell the two kinds apart by the
shapes in an op's instruction text (an extent over the configuration's `cols` is tall), and
`qr_mxu_roofline_pct` holds the call's device time against the Householder flop count at the bf16 peak.
Since PR 35 a call in the Gram form also counts `qr.tall.kernel` / `qr.tall.xla`: which form the
products over the tall operand have in its program (`core.linalg.qr._panels_serve`, from backend, dtype
and shape: on a TPU with x64 off, f32, `n` a multiple of 128 up to 1024, more than one of the kernels'
row blocks, the two Pallas kernels of `core/linalg/_pallas_qr.py`, which do only the blocks that the
Gram matrix's symmetry and the triangle of `R^-1` leave; elsewhere XLA's whole products). A device
trace shows the kernels by name: `qr.tall.gram` (the first Gram matrix) and `qr.tall.apply` (`Q1 = A
R1^-1` with the Gram matrix of `Q1`, the repair step's product, the finish), under the scopes above.

The L1 family (since PR 32): once per `KMedians.fit` / `KMedoids.fit` the counter
`kmedians.step.select.pallas` / `.xla` (`kmedoids.step.select.*`) says which form of the passes
the fit's program runs, as `cluster._pallas_l1.l1_passes_serve` decided from backend, dtype, shape and
split: Pallas kernels on a TPU for tall narrow f32, `jax.numpy` elsewhere; and, since PR 33,
`kmedians.step.select.gather` (`kmedoids.*`) where that program was built with the gathering pass
(`core._pallas_select.gather_pays`: the kernels, from 2^17 rows a cluster and device on). Inside the one fit
program the device ops of an iteration lie under `jax.named_scope("kmedians.assign")` (the L1 assignment;
also the fit's label pass) and `jax.named_scope("kmedians.select")` (the counting selection of all k x d
medians and, for `KMedoids`, the snap); the scopes are named for the estimator (`kmedoids.*`). One op
named `.pass` is one whole read of `X`, for both estimators: `kmedians.assign.pass` (one an iteration,
one for the labels) and `kmedians.select.pass`, which three kernels carry: the counting pass (a digit of
the radix selection: sixteen an iteration for f32 where the program does not gather; where it does, as
many as the counts ask for, since PR 37: after every digit they say how many keys the windows hold, and
the selection counts on `X` until no feature's windows hold over one row in 768
(`core._pallas_select.crowded`), four digits at least and twelve at most: eight on unit blobs near zero,
eleven on the same blobs around 10, twelve around 100),
the gathering pass (one an iteration: it keeps the keys still in their (cluster, feature) pair's
window, its newest bracket with 32 keys and the one above, in an array of 1.6 % of `X`'s size) and the
successor pass (the upper middle value of the even counts: only where the selection ends on `X`). The ops that finish the selection on the kept keys (a count by
cluster, the digits not counted on `X`, one successor) are named `kmedians.select.candidates` and read no `X`.
`breakdown.device_ops` shows all of them by these names, and the benchmark's readers
`kmedians_assign_ms_per_call`, `kmedians_select_ms_per_call`, `kmedians_x_reads_per_call` and
`kmedians_pass_hbm_pct` find the passes by them (an op is named by the text before ` = `). How often the
selection counted on `X`, and how often it went back there, the device trace says:
`kmedians_x_reads_per_call` is `max_iter x (1 + digits + 1) + 1` when every iteration finished on the kept
keys: 51 at five iterations that gathered after eight digits, 56 after nine (every iteration before PR 37),
one more for each iteration that counted one more digit; it grows by the digits left and the successor
(nine reads after eight digits) for each iteration in which a lane position held more kept keys than
slots (rows sorted by a feature, many equal values), and by 1 (the successor pass alone) for each one in
which only an upper middle value lay beyond its window (a median within 1e-6 of zero, where f32 keys are
sparse). Where the windows still hold more keys than the slots are made for after the twelfth digit (many
equal values, values a thousand noise widths from zero), the
gathering pass is told to skip: its op is there, runs empty over one block (under a tenth of a read's time:
no read to `kmedians_x_reads_per_call`, which takes an op under half the median pass for a sliver), and
the iteration has 18 reads as before PR 33.

`ht.percentile` / `ht.median` and the scalers (since PR 38). The selection above is `core/_selection.py`'s
(its kernels `core/_pallas_select.py`'s), and `ht.percentile` along axis 0 of a 2-D array runs it for all
rows (every row counts for every `q`; no label array). Once a call, `percentile.select.pallas` / `.xla` /
`.sort` says which form it took, as `core.statistics._selection_form` decided from backend, dtype, shape,
axis and split: the kernels on a TPU for f32 with a multiple of 8 under 128 columns, on one device or in
equal split-0 shards, from 2^17 rows a target and device on (then also `percentile.select.gather`: the
program has the gathering pass); the `jax.numpy` form of the same passes for f32 / f64 split 0 in equal
shards over more than one device from 2^13 rows a device on; the sort everywhere else. The one program's
device ops lie under `jax.named_scope("percentile.select")`, and the kernels carry the names the
KMedians kernels carry, under the caller's prefix: `percentile.select.pass` is one whole read of `X` (the
first digit's pass, which also looks for NaNs; a counting pass a digit after it, as many as the counts ask
for; the gathering pass; the successor pass, only where the selection ends on `X`: a spill, windows still
crowded after twelve digits, or two targets' windows that overlap without being the same),
`percentile.select.candidates` the small kernels over the kept keys. The benchmark's readers
`percentile_select_ms_per_call`, `percentile_x_reads_per_call` and `percentile_pass_hbm_pct` find them by
these names; all `q` of a call (up to eight distinct rank pairs a batch) share the passes, so the reads do
not grow with them. `RobustScaler.transform` / `inverse_transform` (`scaler.transform`, one program: one
read and one write of the table) run, where `core._pallas_select.tall_narrow_serves`, the kernel named
`scaler.transform.pass` under `jax.named_scope("scaler.transform")`, which `scaler_transform_ms_per_call`
and `scaler_transform_hbm_pct` read; elsewhere the same expression is XLA's fusion in the same program.

`RobustScaler.fit_transform` as one program (since PR 39). Once a call, `scaler.fit_transform.fused` /
`scaler.fit_transform.staged` says which form it took (as `kmeans.step.fused` / `.xla` do for a fit). Fused:
the builder `scaler.robust_fit_transform` composes the traced bodies of `percentile.select`,
`scaler.robust_stats` and `scaler.transform` into ONE jitted program `x -> (y, center, iqr)`, keyed like
its parts, under `shard_map` on a mesh where they are; the outputs and temporaries of the whole call are
placed at one launch and nothing returns to the host between the fit and the transform
(`launches_per_call` 1). The device ops keep their scopes and names (`percentile.select.pass`,
`.candidates`, `scaler.transform.pass`), so the readers above read the fused call as they read the staged
one; the call counts `percentile.select.pallas` / `.xla` (and `.gather`) as its `percentile` call would
have, and `center_`, `iqr_` and the result equal the staged call's bit for bit. Staged (`fit(x).transform(x)`:
what `percentile` would sort, i.e. small, integer, complex, planar tables and other widths; both flags off)
and `fit`, `transform`, `inverse_transform` called by themselves keep the three programs above.
""",
}


def first_line(obj) -> str:
    if isinstance(obj, (int, float, complex, str, tuple)):
        return "constant"  # builtins' type docstrings are noise
    doc = inspect.getdoc(obj) or ""
    line = doc.split("\n", 1)[0].strip()
    return line.replace("|", "\\|")


def sig_of(obj) -> str:
    try:
        s = str(inspect.signature(obj))
        s = s if len(s) <= 80 else s[:77] + "...)"
        return s.replace("|", "\\|")  # PEP 604 unions would break the table
    except (TypeError, ValueError):
        return ""


def rows(mod, prefix):
    names = sorted(set(getattr(mod, "__all__", []) or dir(mod)))
    out = []
    for name in names:
        if name.startswith("_"):
            continue
        try:
            obj = getattr(mod, name)
        except AttributeError:
            continue
        if inspect.ismodule(obj):
            continue
        kind = "class" if inspect.isclass(obj) else (
            "function" if callable(obj) else "data"
        )
        sig = sig_of(obj) if kind != "data" else ""
        out.append(f"| `{prefix}.{name}{sig}` | {kind} | {first_line(obj)} |")
    return out


def _assert_sections_cover_packages() -> None:
    """A new public submodule must be added to SECTIONS (or the
    exclusion list) — otherwise 'every public export' would silently
    become false and the freshness test could not notice."""
    covered = {id(mod) for _, mod, _ in SECTIONS}
    # heat_tpu.core.* is flattened into the ht namespace (its exports are
    # the first section); datasets is bundled data, version is metadata
    excluded = {"core", "version", "functional", "datasets"}
    missing = []
    for name in dir(ht):
        if name.startswith("_") or name in excluded:
            continue
        obj = getattr(ht, name)
        if not inspect.ismodule(obj):
            continue
        mod_name = getattr(obj, "__name__", "")
        if not mod_name.startswith("heat_tpu") or mod_name.startswith("heat_tpu.core"):
            continue
        if id(obj) not in covered:
            missing.append(name)
    assert not missing, f"public submodules missing from SECTIONS: {missing}"


def render() -> str:
    _assert_sections_cover_packages()
    sections = [(title, rows(mod, prefix)) for title, mod, prefix in SECTIONS]
    total = sum(len(r) for _, r in sections)
    lines = [
        "# API reference",
        "",
        "Every public export, generated by `scripts/gen_api_docs.py`",
        "(the analog of the reference's sphinx autosummary site). One-line",
        "summaries come from the docstrings, which carry the reference",
        "file:line parity citations.",
        "",
        f"**{total} public exports.**",
        "",
    ]
    for title, r in sections:
        lines += [f"## {title}", ""]
        if title in NOTES:
            lines += [NOTES[title]]
        lines += ["| export | kind | summary |", "|---|---|---|"]
        lines += r
        lines.append("")
    return "\n".join(lines) + "\n"


def main() -> None:
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "docs", "API.md")
    text = render()
    with open(path, "w") as f:
        f.write(text)
    n_rows = sum(1 for ln in text.splitlines() if ln.startswith("| `"))
    print(f"wrote {path}: {n_rows} exports")


if __name__ == "__main__":
    main()
