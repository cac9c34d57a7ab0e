"""TPU-native local radix sort: fused key+index sort feeding the
distributed sort networks.

``sort_1gb`` is the repo's weakest chip row (ROADMAP "sort": flat at
~208-216 Melem/s across verdicts): the single-chip local sort is
``lax.sort`` — an O(n log² n) comparison network whose stage count, not
HBM bandwidth, is the cost. The reference makes sort a first-class
distributed primitive (sample-sort + Alltoallv, HeAT paper §4); this
repo's distributed layer already replaced the Alltoallv with static
columnsort/odd-even schedules (core/parallel.py). This module is the
same move one level down: the per-chip LOCAL sort becomes an explicit
algorithm instead of one opaque ``lax.sort`` call, under capability
gates with ``lax.sort`` as the numerical oracle and fallback.

Three engines behind one dispatcher:

* **LSD radix** (``_radix_sort_xla`` + the Pallas block kernel): 8-bit
  digits, histogram + exclusive scan + stable rank + permutation-apply.
  The XLA formulation computes the histogram as a one-hot MATMUL
  (``ones @ onehot`` — MXU-friendly) and the stable scatter as a
  unique-index scatter; the Pallas TPU kernel runs the identical pass
  entirely in VMEM with the exclusive scan as a strict-upper-triangular
  matmul and the stable scatter as an EXACT one-hot permutation matmul
  (8-bit byte planes stage u32 words through f32 losslessly: every
  product is ``1.0 * v`` with ``v ≤ 255``, bf16-exact even if the MXU
  rounds its inputs). ``interpret=True`` runs
  the same kernel logic on CPU, so tier-1 exercises it without a TPU.
  Gated to VMEM-block sizes — the compiler generation in this container
  (no gather/scatter/dynamic-lane primitives in Mosaic) cannot express
  a bandwidth-rate global scatter, so the radix engine is the BASE CASE,
  not the 128M-element path (docs/PERF.md "Sort" has the arithmetic).

* **Blocked columnsort** (``_columnsort_local``): Leighton's network —
  the exact schedule ``parallel._columnsort_program`` runs over ICI —
  applied single-chip with the two all-to-alls as free HBM transposes:
  4 BATCHED row sorts (p rows of B = n/p elements) + 3 relayout passes
  replace one monolithic ``lax.sort``. Batched minor-dim sorts are the
  shape XLA's TPU sort emitter blocks into VMEM best; validity is the
  same Leighton bound the distributed program gates on (B ≥ 2(p-1)²,
  p | B), made unconditional here by sentinel padding to p·B.

* **``lax.sort``**: the oracle. Every kernel path produces the EXACT
  oracle argsort indices — the (key, index) pair is a distinct total
  order, so any correct sort agrees — and values equal under the
  comparator (−0.0 and NaN payload bits come back canonicalized, the
  transform's two collapsed tie classes). The tests pin both.

Dispatch: ``HEAT_TPU_SORT_KERNEL=0`` forces the oracle everywhere (the
escape hatch), ``=1`` forces the kernel family (tests/CI), and the
default ``auto`` keeps ``lax.sort`` off-TPU and AUTOTUNES on TPU for
large 1-D sorts — one timed probe per (n, dtype, form), cached, so a
path that loses on the real chip can never regress a workload.
"""

from __future__ import annotations

import functools
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core import gates as _gates
from ..observability.tracing import span as _span

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

__all__ = [
    "to_sortable",
    "from_sortable",
    "local_sort",
    "block_sort",
    "sort_plan",
    "last_decisions",
]

# ---------------------------------------------------------------------- #
# capability gates                                                       #
# ---------------------------------------------------------------------- #
_RADIX_XLA_MAX = 1 << 12     # one-hot/rank matrices are O(n·256) and O(n²)
_PALLAS_BLOCK = 512          # elements per VMEM-resident kernel block
_VMEM_SORT_LOG2 = 20         # ~elements of a (key,idx) pair set resident in
                             # VMEM during a comparison sort (8 B/elem ≈ 8 MB)


def _mode() -> str:
    v = _gates.get("HEAT_TPU_SORT_KERNEL", "auto").strip().lower()
    if v in ("0", "off", "false"):
        return "0"
    if v in ("1", "on", "true", "force"):
        return "1"
    return "auto"


def _inc(name: str) -> None:
    from ..observability import telemetry

    telemetry.inc(name)


# ---------------------------------------------------------------------- #
# monotone bit transforms: dtype <-> radix-sortable unsigned             #
# ---------------------------------------------------------------------- #
_UINT_OF_BITS = {8: jnp.uint8, 16: jnp.uint16, 32: jnp.uint32, 64: jnp.uint64}


def _uint_dtype(itemsize: int):
    if itemsize == 8 and not jax.config.jax_enable_x64:
        return None  # no 64-bit lanes on this platform policy
    return _UINT_OF_BITS.get(itemsize * 8)


def transformable(dtype) -> bool:
    """True when ``to_sortable``/``from_sortable`` serve this dtype."""
    dt = jnp.dtype(dtype)
    if _uint_dtype(dt.itemsize) is None:
        return False
    return (
        jnp.issubdtype(dt, jnp.floating)
        or jnp.issubdtype(dt, jnp.signedinteger)
        or jnp.issubdtype(dt, jnp.unsignedinteger)
    )


def to_sortable(x: jax.Array) -> jax.Array:
    """Map ``x`` to an unsigned integer array of the same width whose
    UNSIGNED order equals ``lax.sort``'s comparator order on ``x``.

    floats: the sign-flip trick — non-negatives get the sign bit set,
    negatives are bitwise-complemented — with XLA's two tie classes
    COLLAPSED so the (key, index) order is exactly the oracle's stable
    order: every NaN (any sign/payload) maps to type-max (the value
    XLA's comparator treats all NaNs as, and the distributed sort's
    pad-sentinel contract: NaN pads sink to the global tail,
    ``manipulations._sort_sentinel_fill``), and −0.0 maps onto +0.0's
    key (XLA ties them). The map is a bijection everywhere else; ints
    are fully bijective (signed: flip the sign bit; unsigned: identity).

    One documented refinement: XLA's comparator runs on FTZ hardware
    and ties every SUBNORMAL with zero; the transform keeps the strict
    IEEE magnitude order for subnormals (values round-trip bit-exact).
    A transform-ordered array is therefore still sorted under XLA's
    comparator — only the argsort tie order among subnormals differs.
    """
    dt = jnp.dtype(x.dtype)
    udt = _uint_dtype(dt.itemsize)
    if udt is None:
        raise TypeError(f"no sortable transform for {dt} on this platform")
    bits = dt.itemsize * 8
    ut = np.dtype(udt).type
    sign = ut(ut(1) << ut(bits - 1))
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return x.astype(udt)
    if jnp.issubdtype(dt, jnp.signedinteger):
        return lax.bitcast_convert_type(x, udt) ^ sign
    if jnp.issubdtype(dt, jnp.floating):
        nmant = jnp.finfo(dt).nmant
        exp_all = ut(((1 << (bits - 1 - nmant)) - 1) << nmant)  # e.g. 0x7F800000
        s = lax.bitcast_convert_type(x, udt)
        isnan = (s & ~sign) > exp_all
        s = jnp.where(s == sign, ut(0), s)  # -0.0 -> +0.0 (XLA ties them)
        # mask = all-ones where negative (two's-complement 0 - 1), else sign
        mask = (ut(0) - (s >> ut(bits - 1))) | sign
        return jnp.where(isnan, ~ut(0), s ^ mask)
    raise TypeError(f"no sortable transform for {dt}")


def from_sortable(u: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`to_sortable`: exact bit round-trip everywhere
    except the two collapsed tie classes, which come back as their
    canonical representative (+0.0; the quiet positive NaN)."""
    dt = jnp.dtype(dtype)
    udt = _uint_dtype(dt.itemsize)
    bits = dt.itemsize * 8
    ut = np.dtype(udt).type
    sign = ut(ut(1) << ut(bits - 1))
    u = u.astype(udt)
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return u.astype(dt)
    if jnp.issubdtype(dt, jnp.signedinteger):
        return lax.bitcast_convert_type(u ^ sign, dt)
    # float: original was negative iff the transformed top bit is 0
    nmant = jnp.finfo(dt).nmant
    exp_all = ut(((1 << (bits - 1 - nmant)) - 1) << nmant)
    qnan = ut(exp_all | (ut(1) << ut(nmant - 1)))  # canonical quiet NaN
    neg = (u >> ut(bits - 1)) ^ ut(1)
    mask = (ut(0) - neg) | sign
    return lax.bitcast_convert_type(
        jnp.where(u == ~ut(0), qnan, u ^ mask), dt
    )


# ---------------------------------------------------------------------- #
# LSD radix — XLA formulation (one-hot matmul histogram; the kernel-    #
# logic reference and the CPU / forced-kernel small-n path)             #
# ---------------------------------------------------------------------- #
def _radix_pass_xla(digits: jax.Array, operands):
    """One stable counting-sort pass by ``digits`` ∈ [0, 256).

    histogram: ``ones(1, n) @ onehot(n, 256)`` — the one-hot matmul
    formulation (rides the MXU on TPU; XLA folds it to a reduce on CPU).
    Precision is pinned HIGHEST: the default TPU precision would feed
    the MXU bf16 inputs and counts ≥ 257 are not bf16-representable —
    a silently wrong destination permutation. rank: exclusive per-digit
    running count from the one-hot's exclusive column scan. scatter:
    destinations are a permutation (unique), so the apply is a
    unique-index scatter per operand.
    """
    n = digits.shape[0]
    oh = (digits[:, None] == jnp.arange(256, dtype=digits.dtype)[None, :])
    ohf = oh.astype(jnp.float32)
    hist = jnp.matmul(
        jnp.ones((1, n), jnp.float32), ohf, precision=lax.Precision.HIGHEST
    )[0]                                                             # (256,)
    excl = jnp.cumsum(hist) - hist                                   # exclusive
    within = jnp.sum((jnp.cumsum(ohf, axis=0) - ohf) * ohf, axis=1)  # (n,)
    base = jnp.take(excl, digits)          # excl[digit] — exact table lookup
    dest = (base + within).astype(jnp.int32)
    return tuple(jnp.zeros_like(t).at[dest].set(t, unique_indices=True) for t in operands)


def _radix_sort_xla(key_positions, operands, bytes_per_word):
    """Stable LSD radix sort of ``operands`` by the lexicographic key
    whose words sit at ``key_positions`` (most-significant FIRST; each
    an unsigned array whose unsigned order is the key order).
    ``bytes_per_word`` bounds the live bytes per word (e.g. 2 for an
    iota < 65536). LSD processes least-significant word first."""
    out = tuple(operands)
    for wi in range(len(key_positions) - 1, -1, -1):
        nbytes = bytes_per_word[wi]
        for b in range(nbytes):
            w = out[key_positions[wi]]
            digits = lax.shift_right_logical(
                w, np.dtype(w.dtype).type(8 * b)
            ).astype(jnp.int32) & 255
            out = _radix_pass_xla(digits, out)
    return out


# ---------------------------------------------------------------------- #
# LSD radix — the Pallas TPU kernel                                      #
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=16)
def _pallas_block_call(n_blocks: int, t: int, pay_bytes: int, key_bytes: int, interpret: bool):
    """Stable (key, payload)-lexicographic LSD radix of independent
    ``t``-element blocks, one block per sequential grid step, entirely
    in VMEM. Per 8-bit pass:

      histogram      one-hot (t, 256) colsum                     (VPU)
      exclusive scan ``hist @ strict_upper(256, 256)``           (MXU)
      stable rank    row-sum of (digit-equal & earlier) matrix   (VPU)
      stable scatter ``P @ data`` with P the destination one-hot (MXU)

    Every matmul is EXACT even if the MXU rounds its f32 INPUTS to
    bf16 (the TPU default-precision behavior): one operand of each dot
    is a 0/1 matrix, and the other never exceeds 255 — u32 words travel
    as FOUR 8-bit byte planes, and the count vectors (values up to t)
    enter the scan/base dots split into their own low/high byte planes,
    recombined by a ×256 f32 add on the exact accumulators. So every
    product is ``1.0 * v`` with v ≤ 255 (bf16-exact) and every sum
    stays an integer < 2^24 in the f32 accumulator. No gather, scatter,
    or dynamic indexing appears in the kernel; the only data-dependent
    movement is the permutation matmul, which is why this formulation
    compiles on Mosaic generations without dynamic-lane addressing."""

    def _byte_planes(w):
        # (t, 1) i32 word -> [(t, 1) f32] * 4, each plane ≤ 255
        return [
            (
                lax.shift_right_logical(w, jnp.full(w.shape, 8 * k, w.dtype)) & 255
            ).astype(jnp.float32)
            for k in range(4)
        ]

    def _recombine(planes):
        # [(t, 1) f32] * 4 -> (t, 1) i32, joined by signed arithmetic and
        # not by shift-and-or: the v5e compiler turns ``lo | (hi << 16)``
        # into a 16-bit float pack that flushes denormal and canonicalises
        # NaN bit patterns (on the chip, PR 22: byte 2 lost in 279 of 512
        # random words). No step here overflows int32.
        b = [p.astype(jnp.int32) for p in planes]
        lo16 = b[0] + b[1] * 256
        hi16 = b[2] + b[3] * 256
        return jnp.where(hi16 >= 32768, hi16 - 65536, hi16) * 65536 + lo16

    def _split_dot(vec_f, mat):
        """``vec @ mat`` with ``mat`` 0/1 and ``vec`` integer-valued
        f32 ≤ 2^16: exact under bf16 input rounding via low/high byte
        planes of ``vec`` recombined in the f32 accumulator."""
        v_i = vec_f.astype(jnp.int32)
        lo = (v_i & 255).astype(jnp.float32)
        hi = lax.shift_right_logical(v_i, jnp.full(v_i.shape, 8, v_i.dtype)).astype(
            jnp.float32
        )
        return (
            jnp.dot(lo, mat, preferred_element_type=jnp.float32)
            + 256.0 * jnp.dot(hi, mat, preferred_element_type=jnp.float32)
        )

    def kernel(k_ref, p_ref, ko_ref, po_ref):
        key = k_ref[...].reshape(t, 1)
        pay = p_ref[...].reshape(t, 1)
        row = lax.broadcasted_iota(jnp.int32, (t, t), 0)
        col = lax.broadcasted_iota(jnp.int32, (t, t), 1)
        earlier = col < row
        bins = lax.broadcasted_iota(jnp.int32, (1, 256), 1)
        upper = (
            lax.broadcasted_iota(jnp.int32, (256, 256), 0)
            < lax.broadcasted_iota(jnp.int32, (256, 256), 1)
        ).astype(jnp.float32)

        passes = [("pay", b) for b in range(pay_bytes)] + [
            ("key", b) for b in range(key_bytes)
        ]
        for which, b in passes:
            w = pay if which == "pay" else key
            digit = (
                lax.shift_right_logical(w, jnp.full(w.shape, 8 * b, w.dtype)) & 255
            )
            eq = digit == digit.reshape(1, t)                       # (t, t)
            rank = jnp.sum(
                jnp.where(eq & earlier, 1.0, 0.0), axis=1, keepdims=True
            )                                                       # (t, 1) f32
            oh = (digit == bins).astype(jnp.float32)                # (t, 256)
            hist = jnp.sum(oh, axis=0, keepdims=True)               # (1, 256)
            excl = _split_dot(hist, upper)                          # (1, 256)
            # base = excl[digit], as onehot @ excl with excl byte-split
            e_i = excl.astype(jnp.int32)
            e_lo = (e_i & 255).astype(jnp.float32).reshape(256, 1)
            e_hi = lax.shift_right_logical(
                e_i, jnp.full(e_i.shape, 8, e_i.dtype)
            ).astype(jnp.float32).reshape(256, 1)
            base = jnp.dot(
                oh, e_lo, preferred_element_type=jnp.float32
            ) + 256.0 * jnp.dot(oh, e_hi, preferred_element_type=jnp.float32)
            # Mosaic's iota is integer-only: compare destinations as i32
            dest = (base + rank).astype(jnp.int32)                  # (t, 1), exact
            perm = (row == dest.reshape(1, t)).astype(jnp.float32)  # (t, t)
            data = jnp.concatenate(
                _byte_planes(key) + _byte_planes(pay), axis=1
            )                                                        # (t, 8)
            moved = jnp.dot(perm, data, preferred_element_type=jnp.float32)
            key = _recombine([moved[:, k : k + 1] for k in range(4)])
            pay = _recombine([moved[:, 4 + k : 5 + k] for k in range(4)])

        ko_ref[...] = key.reshape(1, 1, t)
        po_ref[...] = pay.reshape(1, 1, t)

    # blocks ride a (n_blocks, 1, t) array: the block's last two dims are
    # then the array's own, which Mosaic's (8, 128) tiling rule requires
    # of a one-row block
    spec = pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0), memory_space=_VMEM)
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, 1, t), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 1, t), jnp.int32),
        ],
        interpret=interpret,
    )


def pallas_serviceable(n: int) -> bool:
    """Shape-level predicate: would the Pallas block kernel serve an
    ``n``-element fused key+index sort?"""
    return 0 < n <= _PALLAS_BLOCK


def _pallas_pair_sort(key_u32: jax.Array, pay_u32: jax.Array, pay_bytes: int = 4):
    """(key, payload)-lexicographic sort of one ≤ ``_PALLAS_BLOCK``
    block via the Pallas kernel (interpret mode off-TPU so the same
    kernel logic runs in tier-1 on CPU). Inputs/outputs are u32.

    Sentinel pads are (max, max) pairs: strictly after every real pair,
    because a real payload never reaches type-max (payloads are either
    an iota < block size or a transformed index whose extent fits the
    index dtype). ``pay_bytes`` may be lowered to 2 ONLY when the caller
    guarantees payloads < 2^16 (the iota-payload fast path)."""
    n = key_u32.shape[0]
    t = _PALLAS_BLOCK
    pad = t - n
    if pad:
        key_u32 = jnp.concatenate([key_u32, jnp.full((pad,), 0xFFFFFFFF, jnp.uint32)])
        pay_u32 = jnp.concatenate(
            [pay_u32, jnp.full((pad,), 0xFFFFFFFF, jnp.uint32)]
        )
    k2 = lax.bitcast_convert_type(key_u32, jnp.int32).reshape(1, 1, t)
    p2 = lax.bitcast_convert_type(pay_u32, jnp.int32).reshape(1, 1, t)
    interpret = jax.default_backend() != "tpu"
    ks, ps = _pallas_block_call(1, t, pay_bytes, 4, interpret)(k2, p2)
    ks = lax.bitcast_convert_type(ks.reshape(t), jnp.uint32)[:n]
    ps = lax.bitcast_convert_type(ps.reshape(t), jnp.uint32)[:n]
    return ks, ps


# ---------------------------------------------------------------------- #
# blocked columnsort — Leighton's network, single-chip                   #
# ---------------------------------------------------------------------- #
def _columnsort_p(n: int):
    """Largest power-of-2 p with rows B = ceil(n/p²)·p satisfying
    Leighton's bound B ≥ 2(p-1)² (and p | B by construction). Bigger p
    means shorter batched sort rows — the VMEM-friendly direction."""
    for p in (256, 128, 64, 32, 16, 8, 4):
        b = -(-n // (p * p)) * p
        if b >= 2 * (p - 1) ** 2:
            return p, b
    return None, None


def _columnsort_local(operands, num_keys: int, p: int, b: int, n: int):
    """Single-chip Leighton columnsort of 1-D ``operands`` (first
    ``num_keys`` are the lexicographic sort keys; operand 0 must be an
    unsigned transformed key so the pad sentinel type-max is a true
    maximum; a second key, when present, is an index operand that never
    reaches ITS type-max, so all-max pad tuples stay strictly last even
    against real type-max primary keys).

    The exact schedule of ``parallel._columnsort_program`` with the
    collectives replaced by their local data-movement equivalents:
    deal/undeal are the two all-to-alls as whole-array transposes, and
    the boundary cleanup is ONE batched (p-1, B) merge-sort instead of
    the two half-shard ppermute exchanges. 4 batched sorts + 3 relayout
    passes total; provably sorted for any input at B ≥ 2(p-1)², p | B.
    """
    pad = p * b - n
    padded = []
    for j, t in enumerate(operands):
        if pad:
            if j < num_keys:
                # sentinel pads are (max, ..., max) key tuples: strictly
                # after every real tuple, because a real SECONDARY key
                # (an index) never reaches its type-max even when the
                # primary key does (NaN sentinels / type-max data)
                fill = jnp.full((pad,), jnp.iinfo(t.dtype).max, t.dtype)
            else:
                fill = jnp.zeros((pad,), t.dtype)
            t = jnp.concatenate([t, fill])
        padded.append(t.reshape(p, b))

    def srt(ts):
        return list(lax.sort(tuple(ts), dimension=1, num_keys=num_keys, is_stable=True))

    def deal(t):
        # all_to_all(tiled) of the per-row round-robin deal, locally:
        # row c of the result is [t[r, q·p + c] for r, then q]
        return jnp.transpose(t.reshape(p, b // p, p), (2, 0, 1)).reshape(p, b)

    def undeal(t):
        # inverse deal: row d position q·p + r is t[r, d·(b//p) + q]
        return jnp.transpose(t.reshape(p, p, b // p), (1, 2, 0)).reshape(p, b)

    ts = srt(padded)                       # 1: sort columns
    ts = srt([deal(t) for t in ts])        # 2-3: deal + sort
    ts = srt([undeal(t) for t in ts])      # 4-5: undeal + sort
    # 6-8: boundary cleanup — every adjacent (bottom-half, top-half)
    # window jointly sorted in one batched pass (rows r and r+1 share
    # window r), then reassembled
    h = b // 2
    tops = [t[:, :h] for t in ts]
    bots = [t[:, h:] for t in ts]
    mid = srt(
        [jnp.concatenate([bt[:-1], tp[1:]], axis=1) for bt, tp in zip(bots, tops)]
    )                                      # (p-1, b)
    out = []
    for tp, bt, md in zip(tops, bots, mid):
        up = jnp.concatenate([tp[0:1], md[:, h:]], axis=0)   # (p, h)
        dn = jnp.concatenate([md[:, :h], bt[p - 1 : p]], axis=0)
        out.append(jnp.concatenate([up, dn], axis=1).reshape(p * b)[:n])
    return tuple(out)


# ---------------------------------------------------------------------- #
# dispatch                                                               #
# ---------------------------------------------------------------------- #
_DECISIONS: dict = {}


def last_decisions() -> dict:
    """Copy of the dispatcher's cached path decisions (and autotune
    timings where one ran): {(n, dtype, form): {"path": …, …}}."""
    return {k: dict(v) for k, v in _DECISIONS.items()}


def _kernel_path_for(n: int, itemsize: int = 4) -> str | None:
    """The kernel-family path serving an n-element 1-D fused sort, or
    None when no gate admits one. The Pallas pair kernel stages words
    through 16-bit f32 planes — 32-bit words only."""
    if itemsize == 4 and pallas_serviceable(n):
        return "pallas"
    if n <= _RADIX_XLA_MAX:
        return "radix_xla"
    if _columnsort_p(n)[0] is not None:
        return "columnsort"
    return None


def _autotune(n: int, dtype_name: str) -> str:
    """Time the eligible paths once on synthetic data of the real shape
    AND key width, and cache the winner. Runs only on TPU, eagerly
    (never under a trace). A candidate the backend refuses to lower is
    recorded under ``"refused"`` with the compiler's message — visible
    in :func:`last_decisions`, never a silent ``inf``."""
    key = (n, dtype_name, "pairs")
    if key in _DECISIONS:
        return _DECISIONS[key]["path"]
    itemsize = jnp.dtype(dtype_name).itemsize
    cand = ["lax"]
    kp = _kernel_path_for(n, itemsize=itemsize)
    if kp == "columnsort":
        cand.append("columnsort")
    # well-mixed deterministic keys of the REAL width (Knuth
    # multiplicative hash of iota) — path costs scale with key bytes
    udt = _uint_dtype(itemsize) or jnp.uint32
    um = np.dtype(udt).type
    u = (jnp.arange(n, dtype=udt) * um(2654435761)) ^ um(0x9E3779B9)
    idx = jnp.arange(n, dtype=jnp.int32)
    timings, refused = {}, {}
    for path in cand:
        fn = jax.jit(functools.partial(_run_pair_path, path=path, n=n))
        try:
            with _span("ht.sync.wait", what="sort.autotune"):
                jax.block_until_ready(fn(u, idx))  # compile + warm
        except Exception as e:  # the backend refused this candidate
            refused[path] = f"{type(e).__name__}: {e}"
            continue
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            with _span("ht.sync.wait", what="sort.autotune"):
                jax.block_until_ready(fn(u, idx))
            best = min(best, time.perf_counter() - t0)
        timings[path] = best
    if not timings:
        raise RuntimeError(f"sort autotune: every candidate was refused: {refused}")
    path = min(timings, key=timings.get)
    _DECISIONS[key] = {
        "path": path, "timings": timings, "refused": refused, "autotuned": True,
    }
    return path


def _run_pair_path(u: jax.Array, idx: jax.Array, *, path: str, n: int):
    """(transformed key, index) pair sort by an explicit path — the
    autotune body and the kernel-route core of ``local_sort``."""
    if path == "lax":
        return lax.sort((u, idx), num_keys=2)
    if path == "pallas":
        su, si = _pallas_pair_sort(u, idx.astype(jnp.uint32), pay_bytes=2)
        return su, si.astype(idx.dtype)
    if path == "radix_xla":
        idx_bytes = 2 if n <= 0xFFFF else 4
        su, si = _radix_sort_xla((0, 1), (u, idx), (u.dtype.itemsize, idx_bytes))
        return su, si
    if path == "columnsort":
        p, b = _columnsort_p(n)
        return _columnsort_local((u, idx), 2, p, b, n)
    raise ValueError(f"unknown sort path {path!r}")


def _decide(n: int, dtype_name: str, concrete: bool, itemsize: int = 4) -> str:
    mode = _mode()
    if mode == "0":
        return "lax"
    if mode == "1":
        return _kernel_path_for(n, itemsize=itemsize) or "lax"
    # auto: lax off-TPU; autotuned on TPU for large 1-D sorts
    if jax.default_backend() != "tpu":
        return "lax"
    if n < (1 << 22):
        return "lax"
    key = (n, dtype_name, "pairs")
    # only AUTOTUNED entries may answer for auto mode — a decision cached
    # by a forced HEAT_TPU_SORT_KERNEL=1 call carries no timing evidence
    # and must not bypass the "never worse than lax.sort" floor
    if key in _DECISIONS and _DECISIONS[key].get("autotuned"):
        return _DECISIONS[key]["path"]
    if not concrete:
        return "lax"  # tracing: no autotune possible, stay on the oracle
    return _autotune(n, dtype_name)



def _index_dtype(n: int):
    """Argsort index dtype: int32 below 2^31 (the common case and the
    only kernel-eligible one); int64 above, where the x64 policy admits
    it (matches the pre-kernel ``manipulations.sort`` iota choice)."""
    if n < 2**31:
        return jnp.int32
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def local_sort(arr: jax.Array, axis: int = -1, descending: bool = False):
    """Fused values+argsort local sort along ``axis`` — the single-chip
    engine under ``ht.sort``'s non-split path.

    Returns ``(values, indices)`` with ``indices`` the STABLE argsort
    (``int32``). Semantics are exactly ``lax.sort``'s total order; the
    kernel paths operate on the monotone u32 transform and recover
    values by the inverse bijection — no gather pass. ``descending``
    sorts on the COMPLEMENTED transform in the same single pass (stable
    ties preserved), replacing the old argsort + take_along_axis route.
    """
    axis = axis % arr.ndim
    n = arr.shape[axis]
    # kernel paths carry the index through 32-bit machinery: huge axes
    # stay on the oracle with a wide-enough iota
    eligible = arr.ndim == 1 and n < 2**31 and transformable(arr.dtype)
    path = (
        _decide(
            n,
            jnp.dtype(arr.dtype).name,
            not isinstance(arr, jax.core.Tracer),
            itemsize=jnp.dtype(arr.dtype).itemsize,
        )
        if eligible
        else "lax"
    )
    if path == "lax":
        if eligible or arr.ndim == 1:
            _inc("sort.kernel.fallback")
        if descending and transformable(arr.dtype) and _mode() != "0":
            # one-pass stable descending: ascending sort of ~transform
            # (HEAT_TPU_SORT_KERNEL=0 keeps the pre-kernel two-pass route
            # below — the transform canonicalizes -0.0/NaN payload bits,
            # and the hatch's contract is byte-identical old behavior)
            u = ~to_sortable(arr)
            iota = lax.broadcasted_iota(_index_dtype(n), arr.shape, axis)
            su, si = lax.sort((u, iota), dimension=axis, num_keys=1, is_stable=True)
            return from_sortable(~su, arr.dtype), si
        if descending:
            indices = jnp.argsort(arr, axis=axis, descending=True, stable=True)
            return (
                jnp.take_along_axis(arr, indices, axis=axis),
                indices.astype(_index_dtype(n)),
            )
        iota = lax.broadcasted_iota(_index_dtype(n), arr.shape, axis)
        return lax.sort((arr, iota), dimension=axis, num_keys=1, is_stable=True)
    _inc("sort.kernel.hit")
    _DECISIONS.setdefault(
        (n, jnp.dtype(arr.dtype).name, "pairs"), {"path": path, "forced": True}
    )
    if isinstance(arr, jax.core.Tracer):
        return _pair_body(arr, path=path, n=n, descending=descending)
    return _pair_program(path, n, jnp.dtype(arr.dtype).name, descending)(arr)


def _pair_body(arr, *, path: str, n: int, descending: bool):
    """transform → pair sort → inverse, as one traceable body (jitted
    per (path, n, dtype, direction) by ``_pair_program`` so the eager
    public call pays ONE dispatch and XLA fuses the transforms into the
    sort's neighbors)."""
    u = to_sortable(arr)
    if descending:
        u = ~u
    idx = jnp.arange(n, dtype=jnp.int32)
    su, si = _run_pair_path(u, idx, path=path, n=n)
    if descending:
        su = ~su
    return from_sortable(su, arr.dtype), si


@functools.lru_cache(maxsize=64)
def _pair_program(path: str, n: int, dtype_name: str, descending: bool):
    return jax.jit(
        functools.partial(_pair_body, path=path, n=n, descending=descending)
    )


def block_sort(operands, dimension: int = 0, num_keys: int = 1, is_stable: bool = True, impl: str | None = None):
    """Drop-in ``lax.sort`` replacement for the LOCAL sort steps of the
    distributed programs (``parallel._columnsort_program`` /
    ``_oddeven_sort_program``) — traceable inside ``shard_map``.

    Default mode emits the identical ``lax.sort`` call (bit-identical
    HLO: the distributed collective census cannot move). With
    ``HEAT_TPU_SORT_KERNEL=1`` and a kernel-serviceable shape (1-D
    operands, ≤ 2 sort keys, transformable key dtypes), the sort runs
    through the radix/columnsort engines instead — still collective-free
    local compute, producing the exact oracle order (the (key, index)
    pair is a distinct total order); key VALUES come back canonicalized
    in the transform's two tie classes (−0.0 → +0.0, NaN payloads →
    quiet NaN), equal under the comparator."""
    operands = tuple(operands)
    if impl is None:
        impl = _mode()
    eligible = (
        impl == "1"
        and dimension == 0
        and all(t.ndim == 1 for t in operands)
        and num_keys <= 2
        and all(transformable(t.dtype) for t in operands[:num_keys])
    )
    if not eligible:
        if impl == "1":
            _inc("sort.kernel.fallback")
        return lax.sort(
            operands, dimension=dimension, num_keys=num_keys, is_stable=is_stable
        )
    n = operands[0].shape[0]
    keys_u = [to_sortable(t) for t in operands[:num_keys]]
    rest = operands[num_keys:]
    work = tuple(keys_u) + rest
    path = _kernel_path_for(n, itemsize=max(t.dtype.itemsize for t in keys_u))
    if path is None:
        _inc("sort.kernel.fallback")
        return lax.sort(
            operands, dimension=dimension, num_keys=num_keys, is_stable=is_stable
        )
    _inc("sort.kernel.hit")
    if path == "pallas" and num_keys == 1 and not rest:
        # values-only small block: ride a synthetic index (dropped)
        su, _ = _pallas_pair_sort(
            keys_u[0].astype(jnp.uint32), jnp.arange(n, dtype=jnp.uint32), pay_bytes=2
        )
        out = (su,)
    elif path == "pallas" and num_keys == 2 and not rest and n <= _PALLAS_BLOCK:
        su, si = _pallas_pair_sort(
            keys_u[0].astype(jnp.uint32), keys_u[1].astype(jnp.uint32)
        )
        out = (su, si)
    elif path in ("pallas", "radix_xla"):
        # general radix reference formulation (pallas shapes that don't
        # match the pair kernel fall through here too)
        bpw = tuple(t.dtype.itemsize for t in keys_u)
        out = _radix_sort_xla(tuple(range(num_keys)), work, bpw)
    else:  # columnsort
        p, b = _columnsort_p(n)
        out = _columnsort_local(work, num_keys, p, b, n)
    restored = tuple(
        from_sortable(out[j], operands[j].dtype) for j in range(num_keys)
    ) + tuple(out[num_keys:])
    return restored


# ---------------------------------------------------------------------- #
# pass-count model (docs/PERF.md "Sort" arithmetic)                      #
# ---------------------------------------------------------------------- #
def sort_plan(n: int, dtype: str = "float32", with_indices: bool = True, path: str | None = None) -> dict:
    """Pass-count and HBM-byte model of an n-element local sort on the
    given path (default: the dispatcher's cached/predicted choice).

    ``lax.sort`` model: a comparison network of L(L+1)/2 merge stages
    (L = ⌈log₂ n⌉); all stages whose exchange span fits the
    VMEM-resident window (s = ``_VMEM_SORT_LOG2`` log₂-elements) fuse
    into ONE streaming pass, and each wider level k > s spills k − s
    passes — so passes = 1 + Σ_{k>s}(k − s). ``columnsort`` replaces
    one depth-L network with 4 batched depth-log₂(B) sorts (each fully
    VMEM-fusable when B ≤ 2^s) + 3 relayout passes. ``radix`` is
    ⌈bits/8⌉ histogram+scatter pass pairs. ``model_bytes`` / t /
    HBM_peak is the achieved fraction of stream peak AT the model's
    pass count (docs/PERF.md "Sort").
    """
    itemsize = jnp.dtype(dtype).itemsize
    ops_bytes = n * itemsize * (2 if with_indices else 1)
    per_pass = 2 * ops_bytes  # read + write every operand byte
    s = _VMEM_SORT_LOG2

    def _net_passes(m: int) -> int:
        # merge levels whose exchange span fits the VMEM window all fuse
        # into one streaming pass; level k > s spills (k - s) passes
        levels = max(int(np.ceil(np.log2(max(m, 2)))), 1)
        return int(1 + sum(k - s for k in range(s + 1, levels + 1)))

    if path is None:
        dec = _DECISIONS.get((n, jnp.dtype(dtype).name, "pairs"))
        path = dec["path"] if dec else (
            "lax" if _mode() != "1" else (_kernel_path_for(n, itemsize) or "lax")
        )
    if path == "columnsort":
        p, b = _columnsort_p(n)
        if p is None:
            path = "lax"
        else:
            passes = 4 * _net_passes(b) + 3
            return {
                "path": "columnsort",
                "p": p,
                "rows_b": b,
                "passes": passes,
                "hbm_bytes": passes * per_pass,
                "model": "4 batched depth-log2(B) sorts + 3 relayouts",
            }
    if path in ("radix_xla", "pallas"):
        key_bits = itemsize * 8
        idx_bits = max(int(np.ceil(np.log2(max(n, 2)))), 1)
        passes = -(-key_bits // 8) + (-(-idx_bits // 8) if with_indices else 0)
        return {
            "path": path,
            "passes": passes,
            "hbm_bytes": passes * per_pass,
            "model": "8-bit LSD: one histogram+scatter pair per digit",
        }
    passes = _net_passes(n)
    return {
        "path": "lax",
        "passes": passes,
        "hbm_bytes": passes * per_pass,
        "model": (
            "L(L+1)/2-stage comparison network, stages fused into HBM "
            f"passes at a 2^{s}-element VMEM window"
        ),
    }
