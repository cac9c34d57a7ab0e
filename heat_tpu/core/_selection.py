"""Exact order statistics of the columns of a tall array by a radix selection
that counts: no sort, no copy of ``X``.

``order_statistics`` finds, for every feature and every target, the keys at
two neighbouring ranks (a median's two middle values, the two order
statistics that bracket a percentile) on ``_pallas_select._to_key``'s integer
image of the values. Which rows count for which target is said in one of two
ways: **by label** (``labels`` given: ``(k, d)`` targets, a row counts for its
own cluster's; KMedians and KMedoids, ``cluster/_kcluster.py``) or **for all
rows** (``labels`` ``None``: ``(q, d)`` targets, every row counts for each;
``statistics.percentile`` along the sample axis). The passes over ``X`` come
as an argument: the ``jax.numpy`` form here (``passes_xla``) or the chip's
kernels (``_pallas_select.select_passes``), both exact, so the result is the
same key bit for bit.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import _pallas_select as _ps
from ._pallas_select import (
    _MOST_DIGITS_ON_X, _N_THR, _RADIX_BITS, _WINDOW_FIRST_DIGIT, _WINDOW_MIN_KEYS, SelectPasses, _from_key, _key_type,
    _to_key,
)

__all__ = ["order_statistics", "passes_xla"]


def _members(labels: jax.Array, k: int) -> jax.Array:
    """(n, k) int32: 1 where the row is of the cluster."""
    return (labels[:, None] == jnp.arange(k)).astype(jnp.int32)


def passes_xla(k: Optional[int] = None) -> SelectPasses:
    """The selection's passes over ``X`` in plain ``jax.numpy``
    (``_pallas_select`` holds the chip's form of the same), by label for
    ``k`` clusters or, with no ``k``, for all rows. Nothing is larger than
    ``X``: the targets are walked, not broadcast. Under a mesh the sums over
    the split sample axis lower to all-reduces. No gather: the selection
    ends on ``X``."""
    if k is not None:
        def count_below(arr, labels, thr0, step):
            key, thr, member_of = _to_key(arr), thr0[labels], _members(labels, k).T
            return jnp.stack([
                jnp.matmul(member_of, (key < thr + t * step).astype(jnp.int32), preferred_element_type=jnp.int32)
                for t in range(_N_THR)
            ])

        def next_above(arr, labels, at):
            key = _to_key(arr)
            top = jnp.iinfo(key.dtype).max
            above = jnp.where(key > at[labels], key, top)
            return jax.lax.map(lambda c: jnp.min(jnp.where((labels == c)[:, None], above, top), axis=0), jnp.arange(k))

        return SelectPasses(count_below, next_above)

    def under(key, thr):
        """int32 (q, d): a feature's keys under each of its ``q`` thresholds."""
        return jax.lax.map(lambda t: jnp.sum(key < t, axis=0, dtype=jnp.int32), thr)

    def count_below(arr, thr0, step):
        key = _to_key(arr)
        return jnp.stack([under(key, thr0 + t * step) for t in range(_N_THR)])

    def next_above(arr, at):
        key = _to_key(arr)
        top = jnp.iinfo(key.dtype).max
        return jax.lax.map(lambda a: jnp.min(jnp.where(key > a, key, top), axis=0), at)

    def first(arr):
        key = _to_key(arr)
        bits = _key_type(arr.dtype)[1]
        step = 1 << (bits - _RADIX_BITS)
        thr = jnp.asarray([(t + 1) * step - (1 << (bits - 1)) for t in range(_N_THR)], key.dtype)
        return under(key, jnp.broadcast_to(thr[:, None], (_N_THR, arr.shape[1]))), \
            jnp.sum(jnp.isnan(arr), axis=0, dtype=jnp.int32)

    return SelectPasses(count_below, next_above, first=first)


def order_statistics(arr: jax.Array, lower: jax.Array, upper: jax.Array, counts: jax.Array, passes: SelectPasses,
                     labels: Optional[jax.Array] = None, first_under: Optional[jax.Array] = None):
    """``(low, high)``, each ``(k, d)`` keys (``_from_key`` gives the
    values): for target ``i`` and feature ``j`` the keys at the 0-based ranks
    ``lower[i, j]`` and ``upper[i, j]`` (``lower`` or ``lower + 1``) among
    the ``counts[i, j]`` values of column ``j`` that count for the target:
    those of the rows labelled ``i`` or, with ``labels`` ``None``, of all
    rows. ``lower``, ``upper`` and ``counts`` are int32 and broadcast to
    ``(k, d)``. ``arr`` holds no NaN among the rows that count, or the caller
    overrides the result (as keys, NaNs lie beyond the infinities).

    A radix selection on ``_to_key``'s integer image, all k x d order
    statistics at once. Every pass counts, by target and feature, the keys
    under each of ``_N_THR`` thresholds that cut the bracket
    ``[base, base + 2**bits)`` evenly, and the bracket that holds rank
    ``lower`` becomes the next: after ``bits / _RADIX_BITS``
    passes ``base`` is that order statistic's key. The upper one is
    the same key where the count under the bracket's end says a duplicate
    fills the next rank, else the least key above (one more pass). Beside
    ``arr`` and ``labels`` it holds O(k x d x thresholds) integers, the
    number of passes does not depend on ``k``, and on a split array the
    counts of the shards are summed before a bracket narrows.

    Where the passes come with a ``gather`` (the kernels, on enough rows:
    ``_pallas_select.gather_pays``) only the first digits are counted on
    ``arr``, and the counts say how many: after every digit they give the
    keys each target's window holds, and the loop stops at the first digit
    (from ``_WINDOW_FIRST_DIGIT`` on) after which no feature's windows hold
    more than the slots are made for (``_pallas_select.crowded``: eight digits
    on unit blobs near zero, ten or eleven where f32 keys lie denser), or at
    ``_MOST_DIGITS_ON_X``. One more pass keeps the windows' keys, and the
    other digits and the upper value are found among them
    (``finish_on_kept``). A count among the kept keys is the count over
    ``arr`` less the keys under the window, whatever digit the loop stopped
    at: the same brackets, so the same key bit for bit.

    ``first_under`` (``_N_THR``, k, d), where the caller has them already,
    are the counts of the first digit (its bracket is the whole key range,
    so for all rows they are one pass of three thresholds whatever ``k``:
    ``passes.first``); the digits on ``arr`` then start at the second."""
    ktype, bits = _key_type(arr.dtype)
    k, d = jnp.broadcast_shapes(lower.shape, (1, arr.shape[1]))
    digits = bits // _RADIX_BITS
    over_x = (arr,) if labels is None else (arr, labels)

    bits_left = lambda p: jnp.asarray(bits - _RADIX_BITS * p).astype(ktype)  # of a bracket after p digits

    def narrow_by(count_below):
        """One digit of every bracket ``(base, keys under its start, keys
        under its end)``; ``count_below(thr0, step)`` gives a pair's keys
        under ``thr0 + t * step``, ``(_N_THR, k, d)``."""

        def narrow(p, state):
            base, under_base, under_end = state
            step = jnp.asarray(1, ktype) << bits_left(p + 1)
            under = count_below(base + step, step)
            digit = jnp.sum((under <= lower).astype(jnp.int32), axis=0)
            # keys under the edges of the four brackets: the chosen one lies between two of them
            edges = jnp.concatenate([under_base[None], under, under_end[None]])
            edge = lambda i: jnp.take_along_axis(edges, i[None], axis=0)[0]
            return base + digit.astype(ktype) * step, edge(digit), edge(digit + 1)

        return narrow

    on_x = narrow_by(lambda thr0, step: passes.count_below(*over_x, thr0, step))

    def digits_on_x(p, state):
        low, _, under_end = jax.lax.fori_loop(p, digits, on_x, state)
        return low, under_end

    def upper_from_x(low, under_end):
        """(lower, upper) key, the upper one by the successor pass."""
        # under_end counts the keys <= low: a duplicate of low fills the upper rank
        return low, jnp.where(under_end > upper, low, passes.next_above(*over_x, low).astype(ktype))

    def note_window(carry):
        """A digit on ``X``, and the pair's window: its newest bracket that
        still holds ``_WINDOW_MIN_KEYS`` keys (none before the fourth), and
        the bracket above it unless that lies past the last key."""
        p, state, window = carry
        base, under_base, under_end = state = on_x(p, state)
        held = under_end - under_base
        fits = (p < _WINDOW_FIRST_DIGIT) | (held >= _WINDOW_MIN_KEYS)
        two = (jnp.asarray(2, ktype) << bits_left(p + 1)) - 1  # from the base to the last key of the bracket above
        wide = bits_left(p + 1) + (base <= jnp.iinfo(ktype).max - two).astype(ktype)
        return p + 1, state, tuple(jnp.where(fits, new, old) for new, old in zip((base, under_base, wide, held), window))

    def windows_crowded(window):
        return _ps.crowded(window[3], arr.shape[0])

    def another_digit_on_x(carry):
        """After ``p`` digits: an offset does not fit under the label yet,
        or the windows hold more keys than the slots are made for and one
        more digit on ``X`` may still pay."""
        p, _, window = carry
        return (p < _WINDOW_FIRST_DIGIT) | ((p < _MOST_DIGITS_ON_X) & windows_crowded(window))

    def finish_on_kept(p, state, window):
        """The digits from the ``p``-th on and the upper value from
        the keys one gathering pass keeps: those of each row's own window
        ``[base, base + 2 ** wide)`` (for all rows: of every target's), by
        target and offset, so that a count
        among them is the count over ``X`` less the keys under ``base``.
        Where the windows still hold more keys than the slots are made for
        (the loop stopped at ``_MOST_DIGITS_ON_X``: the pass is told to skip),
        two targets' windows overlap without being the same, or a slot
        spilled, the digits are counted on ``X``; there, and where
        the upper rank of some pair lies beyond its window (a median within
        1e-6 of zero, where f32 keys are sparse), the successor pass runs on
        ``X``."""
        base, under_base, wide, _ = window
        skip, owner = windows_crowded(window), None
        if labels is None:  # windows of two targets may meet: the first of those with the same one keeps its keys
            owner, clash = _ps.window_owners(base, wide)
            skip = skip | clash
        kept, spilled = passes.gather(*over_x, base, wide, skip)
        ahead, in_window = _ps.kept_by_target(passes, kept, k, owner)
        beyond = (in_window > 0) & (upper - under_base >= in_window)

        def count_below(thr0, step):
            off = thr0 - base + step * jnp.arange(_N_THR, dtype=ktype)[:, None, None]
            return under_base + _ps.kept_under(passes, kept, off, ahead, owner)

        def digits_on_kept(state):
            low, _, under_end = jax.lax.fori_loop(p, digits, narrow_by(count_below), state)
            return low, under_end

        def upper_from_kept(low, under_end):
            return low, jnp.where(under_end > upper, low, base + _ps.kept_next(passes, kept, low - base, owner))

        def among_kept(state):
            return upper_from_kept(*digits_on_kept(state))

        def back_to_x(state):
            return upper_from_x(*jax.lax.cond(spilled, functools.partial(digits_on_x, p), digits_on_kept, state))

        return jax.lax.cond(spilled | jnp.any(beyond), back_to_x, among_kept, state)

    first = jnp.full((k, d), -(1 << (bits - 1)), ktype)
    none = jnp.zeros((k, d), jnp.int32)
    state = (first, none, jnp.broadcast_to(counts, (k, d)))
    p0 = 0
    if first_under is not None:
        p0, state = 1, narrow_by(lambda thr0, step: first_under)(0, state)
    if passes.gather is None:
        return upper_from_x(*digits_on_x(p0, state))
    # the first digit's bracket is every pair's first window
    window = (first, none, none.astype(ktype), none)
    return finish_on_kept(*jax.lax.while_loop(another_digit_on_x, note_window, (jnp.int32(p0), state, window)))
