"""Deliberately-bad programs for the ``ht.analysis`` golden-finding
tests. Each function violates one or more shardlint IR rules ON PURPOSE
— tier-1 asserts ``ht.analysis.check`` reports them (and that the
shipped TSQR/hSVD/ring-attention programs stay clean). Keep the
violations obvious and commented; these are the analyzer's oracle.
"""

import jax

import heat_tpu as ht


def bad_program(x, debug=False):
    """Three violations in one program:

    - SL101: a bare sharding constraint pins the operand to the OTHER
      split mid-expression — an implicit GSPMD all-to-all no plan
      issued. (The public ``resplit`` no longer models this: it routes
      through ``ht.redistribution`` whose programs stamp their plan id
      into the HLO and downgrade to info — the accident this rule
      exists for is exactly the UNstamped relayout.)
    - SL102: a replicated constraint materializes a copy of the whole
      array (an all-gather of every byte);
    - SL105: the replicated output has the same aval as the argument but
      the buffer is not donated;
    - SL106: the debug arm reads the device value on the host — never
      taken at trace time, only the source scan can see it.
    """
    import jax.numpy as jnp
    from jax import lax

    phys = x._phys
    y = jnp.exp(lax.with_sharding_constraint(phys, x.comm.sharding(phys.ndim, 1)))
    z = lax.with_sharding_constraint(phys, x.comm.sharding(phys.ndim, None))
    if debug:
        host = jax.device_get(z)  # shardlint: ignore[SL201] -- fixture
        print(float(host.sum()))
    return y, z


def widening_program(x):
    """SL104: promotes the f32 operand to f64 mid-program (an accidental
    64-bit astype — no input justifies the widening)."""
    return ht.sum(x.astype(ht.float64) * 2.0)


def gather_reduce_program(x):
    """SL103 (and SL102): gathers the whole operand replicated, then
    reduces it — the textbook case where reduce-scatter (or a local
    reduce + tiny all-reduce, what ``ht.sum`` on the SHARDED array
    compiles to) moves O(1/p) of the bytes."""
    return ht.sum(x.resplit(None))


def donated_program(x):
    """Clean twin of ``bad_program``'s SL105 arm: same aliasable output,
    but the wrapper donates the argument."""
    return ht.exp(x)


def int8_wire_program(x):
    """SL104 (narrowing arm): a hand-rolled UNSCALED ``astype(int8)``
    feeding a psum — the gradient-compression accident: values outside
    [-128, 127] truncate and the int8 reduction wraps. The sanctioned
    narrowing is the STAMPED block-quantized wire codec
    (``heat_tpu.kernels.quant``: per-tile scales, reserved special
    codes, ``wire_codec_<mode>`` named scope) — only codec-stamped
    converts downgrade to info; this one trips at error severity."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    comm = x.comm
    phys = x._phys

    def body(xl):
        # no scale, no specials, straight into the collective
        return lax.psum(xl.astype(jnp.int8), comm.axis_name).astype(jnp.float32)

    spec = P(*(comm.axis_name if k == 0 else None for k in range(phys.ndim)))
    out = P(*(None,) * phys.ndim)
    return shard_map(
        body, mesh=comm.mesh, in_specs=(spec,), out_specs=out, check_vma=False
    )(phys)


def flat_dcn_a2a_program(x):
    """SL107 (cross-tier collective not decomposed): a hand-rolled FLAT
    all-to-all whose replica group spans every device — at a two-tier
    topology its whole payload completes at DCN speed (~8x ICI). The
    sanctioned form is the planner's ``hierarchical-a2a`` (intra-slice
    pivot + inter-slice exchange of pre-packed per-slice rows), whose
    stamped programs downgrade to info; this unstamped flat exchange
    trips the rule at warn/error when ``check(..., topology="SxC")``
    (or ``HEAT_TPU_TOPOLOGY``) declares a tiered mesh — and is
    perfectly clean at a flat topology, which is why SL101 alone never
    catches it."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    comm = x.comm
    phys = x._phys

    def body(xl):
        return lax.all_to_all(xl, comm.axis_name, 0, 0, tiled=True)

    spec = P(*(comm.axis_name if k == 0 else None for k in range(phys.ndim)))
    return shard_map(
        body, mesh=comm.mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )(phys)


def ppermute_ring_program(x):
    """SL101: a hand-rolled ppermute relayout loop with NO plan stamp —
    every hop ships the whole local shard around the ring (an all-gather
    in disguise, (p-1)x the bytes of a planned exchange). The planner's
    own ring/pipelined programs run under ``redist_plan_<id>`` /
    ``cmatmul_ring_<tag>`` named scopes and downgrade to info; the
    accident SL101 exists for is exactly this UNstamped chain."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    comm = x.comm
    p = comm.size
    phys = x._phys

    def body(xl):
        acc = xl
        for d in range(1, p):
            acc = lax.ppermute(
                acc, comm.axis_name, [(s, (s + 1) % p) for s in range(p)]
            )
        return acc

    spec = P(*(comm.axis_name if k == 0 else None for k in range(phys.ndim)))
    return shard_map(
        body, mesh=comm.mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )(phys)


def over_budget_program(x):
    """SL301 (ISSUE 10): holds three full-size intermediates live
    simultaneously — the liveness peak is ~4x the operand's shard, so
    under a tiny forced budget (``memcheck(..., hbm_bytes=...)`` or
    ``HEAT_TPU_HBM_BYTES``) the static estimate overcommits HBM and the
    check reports SL301 at error severity BEFORE any dispatch OOMs.
    Under the default 16 GiB budget the same program is clean — the
    rule prices programs against the deployment target, it does not
    punish intermediates per se."""
    a = ht.exp(x)
    b = ht.sqrt(ht.abs(x) + 1.0)
    c = a * b
    return a + b + c  # a, b, c all live at the final combine


def dropped_donation_program(x):
    """SL302 (ISSUE 10): the caller DONATES ``x`` (the test wraps this
    in ``ht.jit(..., donate_argnums=0)``), but the only output is half
    the rows — no output matches the donated aval, XLA cannot alias the
    buffer, and the donation is silently dropped: the compiled module
    carries no ``input_output_alias`` entry for the parameter while the
    caller believes the HBM was reclaimed. SL105's bookkeeping alone
    cannot see this (donation WAS declared); only the executable-level
    check can."""
    return ht.exp(x)[: x.shape[0] // 2]


def replicated_liverange_program(x):
    """SL303 (ISSUE 10): materializes a REPLICATED copy of the whole
    operand (``resplit(None)`` — every device holds all the bytes) and
    then keeps it live across a two-collective resplit round trip
    before finally consuming it. The planner's peak accounting budgets
    each exchange's transients, but the replicated value's residency
    rides across the whole chain unseen — exactly the live-range
    materialization memcheck's liveness analysis exists to surface."""
    g = x.resplit(None)              # replicated materialization, held ...
    y = x.resplit(1).resplit(0)      # ... across two collective steps
    return g * 1.0 + y


# --------------------------------------------------------------------- #
# pass 4 (ISSUE 12): gatecheck + racecheck golden bad fixtures           #
# --------------------------------------------------------------------- #
_donating_double = None


def use_after_donate_program(x):
    """SL401: the inner program DONATES its operand (ht.jit
    donate_argnums — resolved through the shared analysis/_donation.py
    resolver), and the caller then reads the donated array again. The
    donating program may already have overwritten the buffer in place;
    on hardware the second read returns garbage nondeterministically,
    which is exactly why the rule is static (jaxpr dataflow: the
    donated invar is dead past the pjit equation that donates it)."""
    global _donating_double
    if _donating_double is None:
        _donating_double = ht.jit(lambda a: a * 2.0, donate_argnums=0)
    y = _donating_double(x)
    return y + x  # x's buffer was donated one line up


def donate_then_done_program(x):
    """Clean twin of ``use_after_donate_program``: same donating inner
    call, but the donated operand is never touched again."""
    global _donating_double
    if _donating_double is None:
        _donating_double = ht.jit(lambda a: a * 2.0, donate_argnums=0)
    return _donating_double(x) + 1.0


#: SL402 (lru arm): a cached program builder that resolves the overlap
#: gate INSIDE its body — the cache key (the parameters) no longer
#: carries the gate, so a HEAT_TPU_REDIST_OVERLAP flip keeps serving
#: the program compiled under the old value. The fix the finding names:
#: resolve at the caller, pass `pipelined` as a parameter (exactly what
#: redistribution/executor.py does).
STALE_KEY_BUILDER_SRC = '''
import functools

from heat_tpu.redistribution.planner import overlap_mode


@functools.lru_cache(maxsize=512)
def _move_program(comm, spec, budget):
    pipelined = overlap_mode() != "0"   # ambient read under the cache
    return (comm, spec, budget, pipelined)
'''

#: SL402 (dict arm): a plan cache whose key tuple DROPS the resolved
#: topology — the planner's own `key = (spec, b, qmode, topo)` with one
#: component deleted, the exact omission class the PR 9/10 hardening
#: lists kept catching by review.
STALE_DICT_KEY_SRC = '''
_plan_cache = {}


def wire_quant_gate():
    return None


def resolve_topology(n):
    return None


def plan(spec, budget):
    qmode = wire_quant_gate()
    topo = resolve_topology(8)
    key = (spec, budget, qmode or "0")   # topo missing from the key
    cached = _plan_cache.get(key)
    if cached is not None:
        return cached
    _plan_cache[key] = spec
    return spec
'''

#: SL403: raw HEAT_TPU_* reads bypassing the registry — a literal get,
#: the hand-rolled fingerprint enumeration, and a containment probe.
RAW_GATE_READ_SRC = '''
import os


def read_gate():
    return os.environ.get("HEAT_TPU_REDIST_OVERLAP", "auto")


def fingerprint():
    return sorted(k for k in os.environ.keys() if k.startswith("HEAT_TPU_"))


def probe():
    return "HEAT_TPU_OOC" in os.environ
'''

#: SL404: the dispatcher's shape with the counts lock MISSING on the
#: client path — the worker mutates under the lock, stats() reads bare.
UNGUARDED_ATTR_SRC = '''
import threading


class BadDispatcher:
    def __init__(self):
        self._counts_lock = threading.Lock()
        self._counts = {"batches": 0}
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self):
        with self._counts_lock:
            self._counts["batches"] += 1

    def stats(self):
        return dict(self._counts)   # client read, no lock
'''

#: SL405: three broken depth-2 skeletons — the inverted loop (consume
#: lap k before issuing lap k+1), the unfenced read (consuming the lap
#: it JUST issued), and the dropped final lap — plus the correct
#: rotation (`good_laps`, the executor's `_run_laps` shape) as the
#: clean pin.
PIPELINE_PROTOCOL_SRC = '''
def inverted_laps(indices, issue, consume, state):
    idx = list(indices)
    prev = issue(idx[0])
    for i in range(1, len(idx)):
        state = consume(state, prev, idx[i - 1])   # consume BEFORE issue
        prev = issue(idx[i])
    return consume(state, prev, idx[-1])


def unfenced_laps(indices, issue, consume, state):
    idx = list(indices)
    prev = issue(idx[0])
    for i in range(1, len(idx)):
        nxt = issue(idx[i])
        state = consume(state, nxt, idx[i])        # consumes the in-flight lap
        prev = nxt
    return consume(state, prev, idx[-1])


def dropped_lap(indices, issue, consume, state):
    idx = list(indices)
    prev = issue(idx[0])
    for i in range(1, len(idx)):
        nxt = issue(idx[i])
        state = consume(state, prev, idx[i - 1])
        prev = nxt
    return state                                    # final prefetch dropped


def good_laps(indices, issue, consume, state):
    idx = list(indices)
    prev = issue(idx[0])
    for i in range(1, len(idx)):
        nxt = issue(idx[i])
        state = consume(state, prev, idx[i - 1])
        prev = nxt
    return consume(state, prev, idx[-1])
'''

#: SL406 (ISSUE 13): the silent-swallow worker — a threaded request
#: loop whose `except Exception` neither re-raises, resolves a future,
#: nor forwards the caught object: the client's future never resolves
#: and the failure becomes a hang. The clean twins show each accepted
#: surfacing shape (typed future failure; forwarding the object into a
#: queue; delegating to an intra-class helper that fails futures).
SWALLOWED_WORKER_EXC_SRC = '''
import threading


class SwallowingWorker:
    def __init__(self):
        self._q = []
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self):
        while True:
            req = self._q.pop()
            try:
                req.run()
            except Exception:
                continue                      # swallowed: future never resolves


class ResolvingWorker:
    def __init__(self):
        self._q = []
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self):
        while True:
            req = self._q.pop()
            try:
                req.future.set_result(req.run())
            except Exception as e:
                req.future.set_exception(e)   # surfaced typed


class ForwardingWorker:
    def __init__(self):
        self._q = []
        self._out = []
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self):
        try:
            for req in self._q:
                self._out.append(req.run())
        except Exception as exc:
            self._out.append(("error", exc))  # forwarded to the consumer


class DelegatingWorker:
    def __init__(self):
        self._q = []
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _fail_all(self):
        for req in self._q:
            req.future.set_exception(RuntimeError("failed over"))

    def _worker(self):
        try:
            for req in self._q:
                req.run()
        except Exception:
            self._fail_all()                  # intra-class resolver helper


class LoggingSwallowWorker:
    def __init__(self, logger):
        self._q = []
        self._log = logger
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self):
        while True:
            req = self._q.pop()
            try:
                req.run()
            except Exception as e:
                self._log.warning("worker died: %s", e)  # log-and-continue: STILL a swallow
'''


# --------------------------------------------------------------------- #
# pass 5 (ISSUE 14): commcheck golden bad fixtures                       #
# --------------------------------------------------------------------- #
def divergent_cond_collective_program(x):
    """SL501: a ``lax.cond`` whose TRUE branch launches a full-axis psum
    is predicated on ``axis_index`` — the device-identity source, never
    replicated. Half the mesh enters the branch and issues the
    collective, the other half skips it: on TPU the psum never matches
    and the mesh hangs silently. The replication lattice proves the
    predicate varying and trips at error."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    comm = x.comm
    phys = x._phys

    def body(xl):
        i = lax.axis_index(comm.axis_name)
        return lax.cond(
            i < comm.size // 2,
            lambda v: lax.psum(v, comm.axis_name),
            lambda v: v * 2.0,
            xl,
        )

    spec = P(*(comm.axis_name if k == 0 else None for k in range(phys.ndim)))
    return shard_map(
        body, mesh=comm.mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )(phys)


def uniform_cond_collective_program(x):
    """Clean twin of ``divergent_cond_collective_program`` — the fix the
    SL501 message names: the predicate is a FULL-AXIS psum of the local
    condition, so every device computes the same boolean and the
    branches stay congruent."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    comm = x.comm
    phys = x._phys

    def body(xl):
        agree = lax.psum((xl.sum() > 0.0).astype(jnp.float32), comm.axis_name)
        return lax.cond(
            agree > 0.0,
            lambda v: lax.psum(v, comm.axis_name),
            lambda v: v * 2.0,
            xl,
        )

    spec = P(*(comm.axis_name if k == 0 else None for k in range(phys.ndim)))
    return shard_map(
        body, mesh=comm.mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )(phys)


def divergent_while_collective_program(x):
    """SL501 (while arm): the loop's continuation predicate reads the
    LOCAL shard (each device's values differ), so devices exit on
    different iterations — and the psum in the body stops matching on
    the first iteration some device has already left."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    comm = x.comm
    phys = x._phys

    def body(xl):
        def cond_fn(c):
            return c[0] < c[1][0, 0]  # local-shard value: per-device trip count

        def body_fn(c):
            return c[0] + 1.0, lax.psum(c[1], comm.axis_name)

        _, out = lax.while_loop(cond_fn, body_fn, (jnp.float32(0.0), xl))
        return out

    spec = P(*(comm.axis_name if k == 0 else None for k in range(phys.ndim)))
    return shard_map(
        body, mesh=comm.mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )(phys)


def open_ring_program(x):
    """SL502: a hand-rolled ppermute whose pairs DROP the wraparound
    edge — ``(s, s+1)`` for ``s < p-1`` only. Device 0 sends but never
    receives, device p-1 receives but never sends: the ring never
    closes and the unmatched device waits forever. The congruence scan
    reads the compiled ``source_target_pairs`` and trips at error; the
    fix it names is ``kernels.cmatmul.grouped_ring_perm`` (the one
    place the complete +1 ring is built)."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    comm = x.comm
    p = comm.size
    phys = x._phys

    def body(xl):
        return lax.ppermute(
            xl, comm.axis_name, [(s, s + 1) for s in range(p - 1)]
        )

    spec = P(*(comm.axis_name if k == 0 else None for k in range(phys.ndim)))
    return shard_map(
        body, mesh=comm.mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )(phys)


def opposite_order_collectives_program(x):
    """SL503 (cycle arm, error): a DIVERGENT cond whose two branches
    issue the same two full-axis collectives in OPPOSITE orders — psum
    then pmax on one side, pmax then psum on the other. Devices taking
    different branches each wait for the collective the other has not
    issued yet: a cross-group dependency cycle in the channel graph
    (also trips SL501 — the divergence is what arms the cycle)."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    comm = x.comm
    phys = x._phys

    def body(xl):
        i = lax.axis_index(comm.axis_name)

        def lo(v):
            return lax.pmax(lax.psum(v, comm.axis_name), comm.axis_name)

        def hi(v):
            return lax.psum(lax.pmax(v, comm.axis_name), comm.axis_name)

        return lax.cond(i < comm.size // 2, lo, hi, xl)

    spec = P(*(comm.axis_name if k == 0 else None for k in range(phys.ndim)))
    return shard_map(
        body, mesh=comm.mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )(phys)


def overlapping_groups_program(x):
    """SL503 (independent arm, warning): two INDEPENDENT grouped psums
    whose group partitions partially overlap — halves vs neighbor pairs
    — with no dataflow ordering between them. Participants shared by
    unequal groups may observe the two collectives in different issue
    orders (the compiler is free to schedule them per-participant).
    Requires an even mesh of >= 4 devices."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    comm = x.comm
    p = comm.size
    phys = x._phys
    halves = [list(range(p // 2)), list(range(p // 2, p))]
    pairs = [[2 * k, 2 * k + 1] for k in range(p // 2)]

    def body(xl):
        a = lax.psum(xl, comm.axis_name, axis_index_groups=halves)
        b = lax.psum(xl * 2.0, comm.axis_name, axis_index_groups=pairs)
        return a + b

    spec = P(*(comm.axis_name if k == 0 else None for k in range(phys.ndim)))
    return shard_map(
        body, mesh=comm.mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )(phys)


def aligned_groups_program(x):
    """Clean twin of ``overlapping_groups_program`` — the fix the SL503
    message names: both psums ride the SAME partition, so every
    participant agrees on the group structure and order cannot
    diverge."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    comm = x.comm
    p = comm.size
    phys = x._phys
    halves = [list(range(p // 2)), list(range(p // 2, p))]

    def body(xl):
        a = lax.psum(xl, comm.axis_name, axis_index_groups=halves)
        b = lax.psum(xl * 2.0, comm.axis_name, axis_index_groups=halves)
        return a + b

    spec = P(*(comm.axis_name if k == 0 else None for k in range(phys.ndim)))
    return shard_map(
        body, mesh=comm.mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )(phys)


#: SL504: a dispatcher-shaped module whose public entry issues the
#: bucket program with NO epoch fence reachable on its intra-module
#: closure — work dispatched across a world re-resolution hangs on
#: devices that are gone instead of failing typed. The clean twin below
#: shows the sanctioned shape (``elastic.check_epoch`` on entry — the
#: serving Endpoint's own idiom since ISSUE 14).
UNFENCED_DISPATCH_SRC = '''
import threading


class BareEndpoint:
    def __init__(self, programs):
        self.programs = programs
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def run(self, batch, bucket):
        return self.programs[bucket](batch)   # no fence on the entry path

    def _worker(self):
        self.run(None, 0)
'''

#: the fenced twin: one ``check_epoch`` call on the entry makes the
#: whole intra-module closure fenced (same reachability SL402 uses).
FENCED_DISPATCH_SRC = '''
from heat_tpu.resilience.elastic import check_epoch


class FencedEndpoint:
    def __init__(self, programs):
        self.programs = programs
        self._token = None

    def run(self, batch, bucket):
        check_epoch(self._token, what="fixture endpoint")
        return self.programs[bucket](batch)
'''


def serving_sync_handler(x):
    """SL106 (ISSUE 9): a serving request handler that reads device
    VALUES on the host mid-request — a debug/logging sync buried in the
    dispatch→result hot path. One such read serializes the dispatcher's
    whole pipeline behind a host round trip (every queued request
    behind it eats the latency), which is exactly why the serving
    budget is ZERO undeclared ``device_get`` between dispatch and
    result; the dispatcher's own fence is ``block_until_ready``
    (completion, no transfer). ``ht.analysis.check`` aborts the trace
    at the concretizing read and reports SL106; the source scan flags
    the line even when the branch is untaken."""
    import jax

    y = x * 2.0
    if getattr(serving_sync_handler, "_debug", True):
        peek = jax.device_get(y._phys)  # shardlint: ignore[SL201] -- fixture
        print("serving batch mean:", peek.mean())
    return y + 1.0


# --------------------------------------------------------------------- #
# pass 6 (ISSUE 17): numcheck golden bad fixtures                        #
# --------------------------------------------------------------------- #
# Pure-jax programs over jnp arrays (numcheck's calling contract admits
# them like check's): the wrong-number class is a property of the traced
# jaxpr's dtypes, not of the DNDarray layer. Each bad fixture has a
# clean twin one fix away — the fix the finding message names.
def low_precision_gram_program(x):
    """SL601: a bf16 gram matrix accumulated IN bf16 — the contraction
    runs over the full feature extent (>= the acc-dim threshold) and
    every MXU pass rounds the partial sum to 8 mantissa bits. The fix
    is ONE argument: ``preferred_element_type=jnp.float32`` (see
    cluster/_pallas.py's gram builders — accumulate wide, store
    narrow)."""
    import jax.numpy as jnp

    return jnp.matmul(x.T, x)  # bf16 @ bf16 -> bf16 accumulator


def f32_accum_gram_program(x):
    """Clean twin of ``low_precision_gram_program``: same bf16 operands,
    same contraction — the accumulator is f32 via
    ``preferred_element_type`` (the sanctioned form SL601's message
    names)."""
    import jax.numpy as jnp

    return jnp.matmul(x.T, x, preferred_element_type=jnp.float32)


def low_precision_reduce_program(x):
    """SL601 (reduce arm, error extent): a raw bf16 reduce_sum over the
    whole axis — ``jnp.sum`` would auto-upcast (and is therefore
    clean), so the bad form binds the primitive the way a custom
    kernel's reference or a transpose rule would."""
    import jax

    return jax.lax.reduce_sum_p.bind(x, axes=(0,))


def upcast_reduce_program(x):
    """Clean twin of ``low_precision_reduce_program``: upcast before the
    sum, narrow after — also exactly what ``jnp.sum(x)`` emits for
    bf16 input."""
    import jax.numpy as jnp

    return jnp.sum(x, axis=0).astype(x.dtype)


def gauss_default_precision_program(ar, ai, br, bi):
    """SL602: the planar-complex Gauss 3-multiply form at DEFAULT MXU
    precision — ``p3 - p1 - p2`` recovers the imaginary part by
    cancellation of products sharing operands, and default (bf16)
    passes turn that into up to 13% relative error on chip (the PR 5
    live defect, re-created)."""
    import jax.numpy as jnp

    p1 = jnp.matmul(ar, br)
    p2 = jnp.matmul(ai, bi)
    p3 = jnp.matmul(ar + ai, br + bi)
    return p1 - p2, p3 - p1 - p2


def gauss_highest_precision_program(ar, ai, br, bi):
    """Clean twin of ``gauss_default_precision_program``: the same form
    with every dot stamped ``Precision.HIGHEST`` — exact f32 MXU
    products, the sanctioned planar lowering (numcheck reports it at
    info, never gating)."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    p1 = jnp.matmul(ar, br, precision=hp)
    p2 = jnp.matmul(ai, bi, precision=hp)
    p3 = jnp.matmul(ar + ai, br + bi, precision=hp)
    return p1 - p2, p3 - p1 - p2


def gauss_pragma_acknowledged_program(ar, ai, br, bi):
    """Pragma twin of ``gauss_default_precision_program``: the same
    cancellation-prone form, acknowledged IN SOURCE — the pragma names
    the rule and the reason, and numcheck downgrades SL602 to info
    (recorded, never gating)."""
    # numcheck: ignore[SL602] -- validated against the f64 reference path
    import jax.numpy as jnp

    p1 = jnp.matmul(ar, br)
    p2 = jnp.matmul(ai, bi)
    p3 = jnp.matmul(ar + ai, br + bi)
    return p1 - p2, p3 - p1 - p2


def bf16_carry_scan_program(x):
    """SL603 (carry arm): a running mean whose loop carry is CAST to
    bf16 before the scan — every lap re-rounds the accumulated state
    to 8 mantissa bits (the KMeans bf16-counts bug, re-created as the
    scan shape)."""
    import jax
    import jax.numpy as jnp

    def body(mean, row):
        return 0.9 * mean + 0.1 * row.astype(mean.dtype), ()

    mean0 = x[0].astype(jnp.bfloat16)  # f32 state narrowed INTO the loop
    mean, _ = jax.lax.scan(body, mean0, x)
    return mean


def f32_carry_scan_program(x):
    """Clean twin of ``bf16_carry_scan_program``: the carry stays f32;
    only the per-row payload may ride narrow."""
    import jax
    import jax.numpy as jnp

    def body(mean, row):
        return 0.9 * mean + 0.1 * row.astype(jnp.float32), ()

    mean0 = x[0].astype(jnp.float32)
    mean, _ = jax.lax.scan(body, mean0, x)
    return mean


def bf16_ef_carry_program(carry, grad):
    """SL603 (cross-program arm): a DP-style error-feedback step that
    returns its residual carry DOWN-CAST to bf16 — the carry rides the
    ``ht.jit`` boundary back in next step, and the residual it stores
    IS the low-order bits the cast throws away (the contract
    optim/dp_optimizer.py keeps by holding its EF carry in f32)."""
    import jax.numpy as jnp

    h = grad + carry                      # compensate
    update = jnp.round(h * 8.0) / 8.0     # coarse quantized apply
    residual = h - update
    return update, residual.astype(jnp.bfloat16)  # carry dies here


def f32_ef_carry_program(carry, grad):
    """Clean twin of ``bf16_ef_carry_program``: the residual carry
    returns in full f32 width."""
    import jax.numpy as jnp

    h = grad + carry
    update = jnp.round(h * 8.0) / 8.0
    return update, h - update


def f64_request_program(x):
    """SL604: requests f64 mid-program. Under the x64-disabled platform
    policy (core/devices.py — TPU runs x64 off) the astype silently
    degrades to f32 at trace time: the jaxpr shows float32 everywhere
    and only the source scan can see the unmet request."""
    import jax.numpy as jnp

    return jnp.cumsum(x.astype(jnp.float64))


def f32_request_program(x):
    """Clean twin of ``f64_request_program``: requests the f32 the
    platform actually provides — the narrowing is visible in the
    source."""
    import jax.numpy as jnp

    return jnp.cumsum(x.astype(jnp.float32))


# --------------------------------------------------------------------- #
# ISSUE 18: sparse-engine fixtures                                      #
# --------------------------------------------------------------------- #
def gather_per_row_spmv_program(comm, m, rows, indices, data, x):
    """ISSUE 18 golden bad-fixture: gather-the-world SpMV.

    The anti-pattern the brick engine exists to avoid — three
    violations:

    - SL101: the dense operand relays to the OTHER split through a bare
      sharding constraint (an implicit all-to-all no redistribution plan
      stamped; the engine routes this through ``comm.reshard_phys``);
    - SL102: the nnz-sharded stored values materialize replicated (an
      all-gather of every stored element — the engine's shard_map local
      program needs only the device's own brick slab);
    - SL103: the gathered values then feed a full dense reduction (the
      per-multiply normalization), where a local reduce + small
      all-reduce moves O(1/p) of the bytes.

    The sparse components arrive as TRACED arguments (the caller must
    not close over them: a closure-captured component is inlined as a
    replicated constant, and the gathers this fixture exists to pin
    vanish from the compiled program).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    # SL101: bare constraint pins the dense operand to split 1
    xs = lax.with_sharding_constraint(x, comm.sharding(x.ndim, 1))
    # SL102: every stored element gathered to every device
    data_r = lax.with_sharding_constraint(data, comm.sharding(1, None))
    idx_r = lax.with_sharding_constraint(indices, comm.sharding(1, None))
    rows_r = lax.with_sharding_constraint(rows, comm.sharding(1, None))
    contrib = data_r[:, None] * jnp.take(xs, idx_r, axis=0)
    y = jax.ops.segment_sum(contrib, rows_r, num_segments=m)
    # SL103: the replicated gather feeds a full reduction
    return y / jnp.sum(data_r)


def make_pagerank_step(comm, m, nb, B, alpha=0.85):
    """The device program of one PageRank sweep — the engine SpMV plus
    the damping/teleport affine map. Pinned LINT-CLEAN (ircheck +
    memcheck + numcheck) by tests/test_analysis.py: the fixpoint loop's
    entire device side must stay collective-free on the local program
    and free of implicit reshards."""
    import jax
    import jax.numpy as jnp

    from heat_tpu.kernels import spmm as kspmm

    spmv = kspmm.spmm_bcsr_program(comm, m, nb, B, 0, 1, "float32", "xla")

    def step(bdata, bcol, brow, bmask, r, teleport):
        y = spmv(bdata, bcol, brow, bmask, r[:, None])
        return y * jnp.float32(alpha) + teleport

    return step


# --------------------------------------------------------------------- #
# ISSUE 19: dense-factorization fixtures                                #
# --------------------------------------------------------------------- #
def gather_inv_program(x, check_cond=False):
    """ISSUE 19 golden bad-fixture: the pre-factorization inverse path
    writ explicit — gather the whole sharded matrix replicated and hand
    the copy to XLA's one-device LU inverse.

    - SL102: the replicated constraint materializes every byte of the
      operand on every device (an all-gather of the full matrix — the
      blocked ring-LU of ``ht.linalg.inv``/``solve`` moves only
      block-panel ppermutes, its clean twin pinned alongside);
    - SL106: the debug arm reads the conditioning estimate back on the
      host — never taken at trace time, only the source scan sees it.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    phys = x._phys
    # SL102: whole-operand replicated materialization
    rep = lax.with_sharding_constraint(phys, x.comm.sharding(phys.ndim, None))
    out = jnp.linalg.inv(rep)
    if check_cond:
        host = jax.device_get(out)  # shardlint: ignore[SL201] -- fixture
        print(float(abs(host).max()))  # SL106: host concretization
    return out
