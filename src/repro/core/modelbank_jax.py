"""JAX-jitted ModelBank: the on-device third backend of the partitioner.

``JaxModelBank`` holds the same padded ``xs[p, k]`` / ``ss[p, k]`` /
``counts[p]`` layout as the numpy :class:`~repro.core.modelbank.ModelBank`,
as ``jnp`` arrays, and evaluates the same three model queries as pure array
ops.  The ``t*`` search of the geometric partitioner runs entirely on device:

  * exponential bracketing as a ``lax.while_loop`` (masked per batch element,
    so a stacked ``[q, p, k]`` bank bisects every column's ``t*``
    simultaneously);
  * bisection as a ``lax.while_loop`` carrying ``(lo, hi, done)`` — the
    ``done`` flag reproduces the numpy path's early-exit semantics exactly,
    so the two backends take bit-identical branch sequences (and the same
    number of trips, which the partition program returns);
  * the greedy integer completion as a masked lexicographic-argmin pass
    (smallest ``(time(d+1), -frac_remainder, index)``) instead of a Python
    heap — one ``O(p)`` argmin per leftover unit, with only the winning
    row's key recomputed, mirroring the lazy-heap refresh;
  * for monotone-time banks (the host-tracked ``monotone`` flag, see the
    "completion modes" section in ``modelbank.py``) the completion instead
    collapses into ONE more fixed-iteration bisection — count units under a
    time threshold via ``floor(alloc_at_time)``, bulk-grant below it, and
    run the argmin loop only for the boundary-tied remainder.  That removes
    the ~p/2 sequential ``while_loop`` iterations that tied the numpy heap
    at p=10^4 and is what lets p=10^5 fleets repartition in milliseconds
    (``benchmarks/partition_scale.py`` completion columns).

Every formula mirrors the numpy implementation expression-for-expression;
with float64 enabled (``jax.config.update("jax_enable_x64", True)`` or the
``jax.enable_x64()`` context) the element-wise ops are IEEE-double
identical to numpy, so allocations match the numpy bank bit-for-bit (the
acceptance gate of ``benchmarks/partition_scale.py --backend jax``).  Without
x64 the math runs in float32 and allocations may differ by a unit — fine for
steering, not for the parity tests.

Dtype plumbing is explicit throughout: the bank's array dtype (float64 under
x64, float32 otherwise) flows into every constant and scalar operand, so no
silent upcasts/downcasts occur inside ``jit``.

``fold_in`` is the vectorized sorted insert that lets DFPA and the
``BalanceController`` keep the bank as a *device-resident carry* across
rounds — one ``[p]``-wide masked shift per round instead of rebuilding the
padded arrays from ``p`` scalar models (the ROADMAP's observation fold-in
item).  The carry buffers are donated to the update: the bank passed to
``fold_in`` is spent, and a holder that must keep reading it takes a
``copy()`` first (or folds with ``donate=False``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .modelbank import ModelBank

try:  # telemetry is optional: the bank runs identically without repro.obs
    from ..obs.telemetry import active as _obs_active
except ImportError:  # pragma: no cover - obs layer absent
    def _obs_active():
        return None

__all__ = ["JaxModelBank", "fetch_partition", "TRIP_LOOPS"]

# The partition program's loops, in the order of its trips vector (see
# ``_partition_units_impl``); telemetry counts them as
# ``partition.trips.<loop>``.
TRIP_LOOPS = (
    "doubling", "bisection", "takeback",
    "threshold_doubling", "threshold_bisection", "greedy",
)


# ---------------------------------------------------------------------------
# Batched model queries (leading batch dims allowed: [..., p, k])
# ---------------------------------------------------------------------------


def _edges(xs, ss, counts):
    last = jnp.maximum(counts - 1, 0)
    last_x = jnp.take_along_axis(xs, last[..., None], axis=-1)[..., 0]
    last_s = jnp.take_along_axis(ss, last[..., None], axis=-1)[..., 0]
    return xs[..., 0], ss[..., 0], last_x, last_s


def _speed(xs, ss, counts, x):
    """Mirror of ``ModelBank.speed`` (NaN on empty rows)."""
    first_x, first_s, last_x, last_s = _edges(xs, ss, counts)
    k = jnp.sum(xs <= x[..., None], axis=-1) - 1
    k = jnp.clip(k, 0, jnp.maximum(counts - 2, 0))
    kp1 = jnp.minimum(k + 1, xs.shape[-1] - 1)
    x0 = jnp.take_along_axis(xs, k[..., None], axis=-1)[..., 0]
    x1 = jnp.take_along_axis(xs, kp1[..., None], axis=-1)[..., 0]
    s0 = jnp.take_along_axis(ss, k[..., None], axis=-1)[..., 0]
    s1 = jnp.take_along_axis(ss, kp1[..., None], axis=-1)[..., 0]
    one = jnp.asarray(1.0, xs.dtype)
    denom = jnp.where(x1 > x0, x1 - x0, one)
    w = (x - x0) / denom
    interior = s0 + w * (s1 - s0)
    s = jnp.where(x <= first_x, first_s, jnp.where(x >= last_x, last_s, interior))
    return jnp.where(counts > 0, s, jnp.asarray(jnp.nan, xs.dtype))


def _time(xs, ss, counts, x):
    zero = jnp.asarray(0.0, xs.dtype)
    return jnp.where(x > zero, x / _speed(xs, ss, counts, x), zero)


# ``alloc_at_time`` is evaluated on every doubling and bisection trip of both
# solves, ~95 times a partition.  So everything in it that does not depend on
# ``t`` (the edge gathers, the slope division, intercepts, cap clips and
# masks) is built once per program into a table with processors on the last
# axis, ``[..., k-1, p]``, lane-dense on the TPU; a trip then runs one fused
# select-and-reduce over it.


class _Segments(NamedTuple):
    """The cap-free part of the table: ``[..., p]`` edges, ``[..., k-1, p]``
    segments (``None`` for a one-knot bank)."""

    nonempty: jnp.ndarray  # counts > 0
    first_x: jnp.ndarray
    first_s: jnp.ndarray
    last_x: jnp.ndarray
    last_s: jnp.ndarray
    x0: Optional[jnp.ndarray]
    x1: Optional[jnp.ndarray]
    m: Optional[jnp.ndarray]  # slope (s1 - s0) / (x1 - x0)
    c: Optional[jnp.ndarray]  # intercept s0 - m * x0
    seg_ok: Optional[jnp.ndarray]  # segment inside the row's knots, x1 > x0


class _Table(NamedTuple):
    """A ``_Segments`` clipped to one solve's caps: every column
    ``alloc_at_time`` reads except ``t``."""

    segs: _Segments
    caps: jnp.ndarray
    first_cap: jnp.ndarray  # min(first_x, caps)
    right_ok: jnp.ndarray  # the t-free half of the right-region test
    live: jnp.ndarray  # caps > 0 and a non-empty row
    x1c: Optional[jnp.ndarray]  # min(x1, caps)
    valid: Optional[jnp.ndarray]


def _segments(xs, ss, counts):
    first_x, first_s, last_x, last_s = _edges(xs, ss, counts)
    k_max = xs.shape[-1]
    if k_max < 2:
        return _Segments(counts > 0, first_x, first_s, last_x, last_s, *[None] * 5)
    one = jnp.asarray(1.0, xs.dtype)
    xt, st_ = jnp.swapaxes(xs, -1, -2), jnp.swapaxes(ss, -1, -2)  # [..., k, p]
    x0, x1 = xt[..., :-1, :], xt[..., 1:, :]
    s0, s1 = st_[..., :-1, :], st_[..., 1:, :]
    seg = jnp.arange(k_max - 1)[:, None]
    denom = jnp.where(x1 > x0, x1 - x0, one)
    m = (s1 - s0) / denom
    seg_ok = (seg < (counts - 1)[..., None, :]) & (x1 > x0)
    return _Segments(
        counts > 0, first_x, first_s, last_x, last_s, x0, x1, m, s0 - m * x0, seg_ok
    )


def _capped(segs: _Segments, caps) -> _Table:
    zero = jnp.asarray(0.0, segs.first_x.dtype)
    x1c = valid = None
    if segs.m is not None:
        capk = caps[..., None, :]
        x1c = jnp.minimum(segs.x1, capk)
        valid = segs.seg_ok & (segs.x0 < capk)
    return _Table(
        segs, caps, jnp.minimum(segs.first_x, caps),
        (caps > segs.last_x) & segs.nonempty, (caps > zero) & segs.nonempty,
        x1c, valid,
    )


def _alloc_from_table(tab: _Table, t):
    """Mirror of ``ModelBank.alloc_at_time`` on a table; ``t`` has the batch
    shape (scalar for a single bank, ``[q]`` for a stacked one)."""
    segs = tab.segs
    dt = segs.first_x.dtype
    zero, one = jnp.asarray(0.0, dt), jnp.asarray(1.0, dt)
    t = jnp.asarray(t, dt)
    tb = t[..., None]  # broadcast against [..., p]

    # Region [0, x_1]: constant speed ss[..., 0].
    best = jnp.minimum(tb * segs.first_s, tab.first_cap)

    # Interior segments, all at once (static branch on the padded width).
    if segs.m is not None:
        x0, x1c = segs.x0, tab.x1c
        tseg = tb[..., None]  # against [..., k-1, p]
        a = one - tseg * segs.m
        b = tseg * segs.c
        ub = b / jnp.where(a != zero, a, one)
        cand = jnp.where(
            a > zero,
            jnp.where(ub >= x0, jnp.minimum(ub, x1c), zero),
            jnp.where(
                a == zero,
                jnp.where(b >= zero, x1c, zero),
                jnp.where(x1c >= ub, x1c, zero),
            ),
        )
        cand = jnp.where(tab.valid, cand, zero)
        best = jnp.maximum(best, cand.max(axis=-2))

    # Region [x_m, cap]: constant speed at the last observed point.
    ub_r = tb * segs.last_s
    right = tab.right_ok & (ub_r >= segs.last_x)
    best = jnp.maximum(best, jnp.where(right, jnp.minimum(ub_r, tab.caps), zero))

    best = jnp.where(tab.live, best, zero)
    return jnp.where(tb > zero, best, zero)


def _alloc_at_time(xs, ss, counts, t, caps):
    return _alloc_from_table(_capped(_segments(xs, ss, counts), caps), t)


@jax.jit
def _agg_products_jit(xs, ss, ts):
    """Segment-slope products ``t*m`` and ``m*x0`` for the aggregation
    kernel, compiled as a SEPARATE executable from ``_agg_alloc_jit`` on
    purpose: within one executable LLVM contracts ``1 - t*m`` and
    ``s0 - m*x0`` into FMAs (observed on XLA:CPU; ``optimization_barrier``
    does not survive the LLVM lowering), which rounds differently from
    numpy's two-op sequence and breaks the numpy/jax aggregate-bank
    bit-parity.  Materializing the products as one executable's OUTPUTS
    forces the standalone rounding — the consumer then only subtracts,
    and contraction cannot cross compiled-executable boundaries."""
    one = jnp.asarray(1.0, xs.dtype)
    x0, x1 = xs[..., :-1], xs[..., 1:]
    s0, s1 = ss[..., :-1], ss[..., 1:]
    denom = jnp.where(x1 > x0, x1 - x0, one)
    m = (s1 - s0) / denom
    tm = ts[..., None, None] * m[:, None]  # [g, T, p, k-1]
    mx0 = m * x0  # [g, p, k-1]
    return tm, mx0


@jax.jit
def _agg_alloc_jit(xs, ss, counts, caps, ts, tm, mx0):
    """Member allocations at per-group sample times — the device half of
    group aggregation: ``[g, p, k]`` bank blocks evaluated at ``[g, T]``
    times give ``[g, T, p]`` member allocs.  Open-codes ``_alloc_at_time``
    with a broadcast time lane, taking the two FMA-contractable products
    precomputed (see ``_agg_products_jit``), so every remaining op is a
    single correctly-rounded IEEE op and the result is bitwise the host
    ``_alloc_at_times`` pass.  The per-group member SUM happens back on
    host to keep the reduction order — and the aggregate bank —
    bit-identical to the numpy backend."""
    dt = xs.dtype
    zero, one = jnp.asarray(0.0, dt), jnp.asarray(1.0, dt)
    xsb, ssb, cb, capb = xs[:, None], ss[:, None], counts[:, None], caps[:, None]
    tb = jnp.asarray(ts, dt)[..., None]  # [g, T, 1] against [g, 1, p]
    first_x, first_s, last_x, last_s = _edges(xsb, ssb, cb)

    best = jnp.minimum(tb * first_s, jnp.minimum(first_x, capb))

    k_max = xs.shape[-1]
    if k_max >= 2:
        x0, x1 = xsb[..., :-1], xsb[..., 1:]
        s0 = ssb[..., :-1]
        seg = jnp.arange(k_max - 1)
        valid = (
            (seg < (cb - 1)[..., None])
            & (x0 < capb[..., None])
            & (x1 > x0)
        )
        x1c = jnp.minimum(x1, capb[..., None])
        tseg = tb[..., None]  # [g, T, 1, 1] against [g, 1, p, k-1]
        a = one - tm
        b = tseg * (s0 - mx0[:, None])
        ub = b / jnp.where(a != zero, a, one)
        cand = jnp.where(
            a > zero,
            jnp.where(ub >= x0, jnp.minimum(ub, x1c), zero),
            jnp.where(
                a == zero,
                jnp.where(b >= zero, x1c, zero),
                jnp.where(x1c >= ub, x1c, zero),
            ),
        )
        cand = jnp.where(valid, cand, zero)
        best = jnp.maximum(best, cand.max(axis=-1))

    ub_r = tb * last_s
    right = (capb > last_x) & (ub_r >= last_x) & (cb > 0)
    best = jnp.maximum(best, jnp.where(right, jnp.minimum(ub_r, capb), zero))

    best = jnp.where((capb > zero) & (cb > 0), best, zero)
    return jnp.where(tb > zero, best, zero)


def _agg_alloc(xs, ss, counts, caps, ts):
    """Two-dispatch device aggregation evaluation (see the two jits)."""
    tm, mx0 = _agg_products_jit(xs, ss, ts)
    return _agg_alloc_jit(xs, ss, counts, caps, ts, tm, mx0)


@jax.jit
def _monotone_lanes_jit(xs, ss, counts):
    """Device mirror of ``modelbank._monotone_check`` (same expressions),
    reduced per *lane*: one bool per leading batch element (a scalar for a
    plain ``[p, k]`` bank, ``[q]`` for a stacked one).  A lane is monotone
    iff every row's time is nondecreasing — knots sorted, speeds positive
    and finite, knot times ordered (``x0 s1 <= x1 s0``)."""
    k = xs.shape[-1]
    zero = jnp.asarray(0.0, xs.dtype)
    pts = jnp.arange(k) < counts[..., None]
    ok_pts = (xs > zero) & jnp.isfinite(xs) & (ss > zero) & jnp.isfinite(ss)
    ok = ~jnp.any(pts & ~ok_pts, axis=(-2, -1))
    if k >= 2:
        x0, x1 = xs[..., :-1], xs[..., 1:]
        s0, s1 = ss[..., :-1], ss[..., 1:]
        seg = jnp.arange(k - 1) < (counts - 1)[..., None]
        ok_seg = (x1 >= x0) & (x0 * s1 <= x1 * s0)
        ok &= ~jnp.any(seg & ~ok_seg, axis=(-2, -1))
    return ok


# ---------------------------------------------------------------------------
# t* search: masked doubling + fixed-iteration bisection
# ---------------------------------------------------------------------------


def _partition_continuous(xs, ss, counts, segs, caps, n, rel_tol, max_steps):
    dt = xs.dtype
    tab = _capped(segs, caps)  # once, before this solve's loops
    zero = jnp.asarray(0.0, dt)
    n = jnp.asarray(n, dt)
    rel_tol = jnp.asarray(rel_tol, dt)
    active = caps > zero

    # Exponential search for an upper bound on t* (per batch element).
    t_init = _time(xs, ss, counts, jnp.minimum(jnp.asarray(1.0, dt), caps))
    hi = jnp.maximum(
        zero, jnp.where(active, t_init, -jnp.inf).max(axis=-1)
    )
    hi = jnp.maximum(hi, jnp.asarray(1e-9, dt))

    def _need(hi):
        return _alloc_from_table(tab, hi).sum(axis=-1) < n

    def dbl_cond(carry):
        hi, i = carry
        return jnp.any(_need(hi)) & (i < 200)

    def dbl_body(carry):
        hi, i = carry
        hi = jnp.where(_need(hi), hi * 2.0, hi)
        return hi, i + 1

    hi, n_dbl = lax.while_loop(dbl_cond, dbl_body, (hi, jnp.asarray(0, jnp.int32)))

    # Bisection with early exit replicated via `done` (set AFTER the update,
    # exactly like the numpy loop's break).  A while_loop, not a fori_loop:
    # once every lane's `done` freezes its values, further iterations are
    # provable no-ops, and rel_tol=1e-12 converges in ~45 steps — running
    # all 200 made the p=10^4..10^5 (and stacked [q, p, k]) partitions
    # ~4x more expensive for bit-identical results.
    # Lanes with n <= 0 start done: their convergence test (hi - lo <=
    # rel_tol * hi with lo pinned at 0) could never fire, so without this
    # they would spin all max_steps for an answer the excess rescale below
    # zeroes out regardless.  Allocations are identical either way; only
    # such lanes' (unused) t_star differs.  The hierarchical inner solve
    # batches empty-share/padded group lanes through here.
    lo = jnp.zeros_like(hi)
    done = jnp.broadcast_to(n <= zero, hi.shape)

    def bis_cond(carry):
        _, _, done, i = carry
        return (~jnp.all(done)) & (i < max_steps)

    def bis_body(carry):
        lo, hi, done, i = carry
        mid = 0.5 * (lo + hi)
        ge = _alloc_from_table(tab, mid).sum(axis=-1) >= n
        hi2 = jnp.where(~done & ge, mid, hi)
        lo2 = jnp.where(~done & ~ge, mid, lo)
        done2 = done | (hi2 - lo2 <= rel_tol * hi2)
        return lo2, hi2, done2, i + 1

    lo, hi, done, n_bis = lax.while_loop(
        bis_cond, bis_body, (lo, hi, done, jnp.asarray(0, jnp.int32))
    )
    t_star = hi

    alloc = _alloc_from_table(tab, t_star)
    total = alloc.sum(axis=-1)
    excess = total - n
    scaled = alloc - (excess[..., None] * (alloc / total[..., None]))
    alloc = jnp.where(((total > zero) & (excess > zero))[..., None], scaled, alloc)
    return alloc, t_star, jnp.stack([n_dbl, n_bis])


@partial(jax.jit, static_argnames=("max_steps",))
def _partition_continuous_jit(xs, ss, counts, caps, n, rel_tol, max_steps):
    return _partition_continuous(
        xs, ss, counts, _segments(xs, ss, counts), caps, n, rel_tol, max_steps
    )


# ---------------------------------------------------------------------------
# Integer partition: floor + masked take-back + completion (threshold-count
# bulk grant for monotone banks, masked-argmin greedy for the remainder)
# ---------------------------------------------------------------------------


def _threshold_prefill(
    segs, caps_i, d0, leftover, t_star, rel_tol, max_steps, fast_mask
):
    """Batched threshold-count bulk completion (monotone-time banks).

    Expression-for-expression mirror of ``partition._threshold_prefill_bank``:
    bisect a time threshold ``t`` on ``count(t) = sum(clip(floor(alloc(t)),
    d0, caps)) - sum(d0)`` with the strict bracket ``count(lo) < leftover <=
    count(hi)`` (masked doubling bracket from ``t*``, after-update early
    exit), bulk-grant everything counted at ``lo``, and hand the >=1
    boundary-tied remainder to the exact greedy.  Leading batch dims are the
    stacked ``[q, p, k]`` bank's columns; lanes with no leftover — or lanes
    routed to the exact per-unit loop by ``fast_mask`` (per-column completion
    routing: a non-monotone column demotes only itself, in the same device
    program) — pass through untouched.
    """
    dt = t_star.dtype
    it = d0.dtype
    tab = _capped(segs, caps_i.astype(dt))  # once, before this solve's loops
    base_total = d0.sum(axis=-1)
    active = (leftover > 0) & fast_mask

    def count(t):
        a = _alloc_from_table(tab, t)
        g = jnp.clip(jnp.floor(a).astype(it), d0, caps_i)
        return g.sum(axis=-1) - base_total, g

    hi = jnp.maximum(t_star, jnp.asarray(1e-9, dt))

    def _need(hi):
        c, _ = count(hi)
        return active & (c < leftover)

    def dbl_cond(carry):
        hi, i = carry
        return jnp.any(_need(hi)) & (i < 200)

    def dbl_body(carry):
        hi, i = carry
        hi = jnp.where(_need(hi), hi * 2.0, hi)
        return hi, i + 1

    hi, n_dbl = lax.while_loop(dbl_cond, dbl_body, (hi, jnp.asarray(0, jnp.int32)))

    # Same early-exit while_loop as the continuous bisection: inactive (or
    # converged) lanes freeze, and the loop stops when all have.
    lo = jnp.zeros_like(hi)
    done = ~active

    def bis_cond(carry):
        _, _, done, i = carry
        return (~jnp.all(done)) & (i < max_steps)

    def bis_body(carry):
        lo, hi, done, i = carry
        mid = 0.5 * (lo + hi)
        c, _ = count(mid)
        ge = c >= leftover
        hi2 = jnp.where(~done & ge, mid, hi)
        lo2 = jnp.where(~done & ~ge, mid, lo)
        done2 = done | (hi2 - lo2 <= rel_tol * hi2)
        return lo2, hi2, done2, i + 1

    lo, hi, done, n_bis = lax.while_loop(
        bis_cond, bis_body, (lo, hi, done, jnp.asarray(0, jnp.int32))
    )
    c_lo, g_lo = count(lo)
    d = jnp.where(active[..., None], g_lo, d0)
    leftover2 = jnp.where(active, leftover - c_lo, leftover)
    return d, leftover2, jnp.stack([n_dbl, n_bis])


def _complete_greedy_one(xs, ss, counts, caps_i, d, rem, leftover):
    """Greedy completion for ONE bank (no leading batch dims; vmapped by the
    caller for stacked banks).

    Repeated masked lexicographic argmin over ``(time(d+1), -rem, index)`` —
    identical tie-breaking to the numpy lazy heap.  The key vector is carried
    and only the winner's entry is rewritten (a scatter, mirroring the heap's
    single-entry refresh), so one leftover unit costs a handful of ``O(p)``
    reduction passes instead of full-array rebuilds.
    """
    dt = xs.dtype
    it = d.dtype
    key0 = jnp.where((d + 1) <= caps_i, _time(xs, ss, counts, (d + 1).astype(dt)), jnp.inf)

    def cond(carry):
        _, leftover, _, _, _ = carry
        return leftover > 0

    def body(carry):
        d, leftover, key, ok, trips = carry
        i0 = jnp.argmin(key)  # first index of the minimum
        m1 = key[i0]
        feasible = jnp.isfinite(m1)

        def tie_break(_):
            # >1 processor shares the exact minimal time: the heap orders
            # them by (-rem, index) — largest fractional remainder wins.
            tie = key == m1
            r = jnp.where(tie, rem, -jnp.inf)
            return jnp.argmax(tie & (r == r.max()))

        i = lax.cond(jnp.sum(key == m1) > 1, tie_break, lambda _: i0, None)
        take = feasible.astype(it)
        d2 = d.at[i].add(take)
        x_new = (d2[i] + 1).astype(dt)
        t_new = _time(xs[i], ss[i], counts[i], x_new)
        new_key = jnp.where((d2[i] + 1) <= caps_i[i], t_new, jnp.inf)
        key2 = key.at[i].set(jnp.where(feasible, new_key, key[i]))
        leftover2 = jnp.where(feasible, leftover - 1, 0)
        return d2, leftover2, key2, ok & feasible, trips + 1

    d, _, _, ok, trips = lax.while_loop(
        cond, body, (d, leftover, key0, jnp.asarray(True), jnp.asarray(0, jnp.int32))
    )
    return d, ok, trips


def _partition_units_impl(
    xs, ss, counts, caps_i, n, min_units, rel_tol, max_steps, fast_mask,
    completion_fast=False,
):
    # `n` and `fast_mask` carry the batch shape (scalars for a plain bank,
    # [q] for a stacked one); `min_units` carries the ROW shape ``[..., p]``
    # (the public API broadcasts its per-lane floors; the hierarchical inner
    # solve passes genuinely per-row floors so padded member rows pin at 0)
    # — per-column unit counts, floors and completion routing all ride the
    # same device program.  This plain impl is also called per group block
    # inside ``_hier_inner_map``'s ``lax.map`` (and under ``shard_map``), so
    # it must stay jit-free; ``_partition_units_jit`` below is the jitted
    # entry point with identical semantics.  Besides ``(d, ok, t_star)`` it
    # returns the trips of each of its loops (``TRIP_LOOPS`` order, int32;
    # greedy trips summed over the lanes of a stacked bank, 0 for a loop a
    # static branch skipped).
    dt = xs.dtype
    it = caps_i.dtype
    n_f = jnp.asarray(n, dt)
    caps_f = jnp.minimum(caps_i.astype(dt), n_f[..., None])  # continuous clip
    segs = _segments(xs, ss, counts)  # once per program, read by every trip
    alloc, t_star, trips_cont = _partition_continuous(
        xs, ss, counts, segs, caps_f, n_f, rel_tol, max_steps
    )

    d = jnp.maximum(min_units, jnp.floor(alloc).astype(it))
    d = jnp.minimum(d, caps_i)
    leftover = jnp.asarray(n, it) - d.sum(axis=-1)
    p = xs.shape[-2]
    idx = jnp.arange(p)

    # -- take-back (min_units overshoot): largest per-unit time first,
    #    round-robin — the stable descending order of the numpy path.
    per_unit = _time(xs, ss, counts, d.astype(dt)) / jnp.maximum(d, 1)
    order = jnp.argsort(-per_unit, axis=-1, stable=True)

    def tb_cond(carry):
        _, leftover, _ = carry
        return jnp.any(leftover < 0)

    def tb_body(carry):
        d, leftover, kk = carry
        i = jnp.take_along_axis(order, (kk % p)[..., None], axis=-1)[..., 0]
        d_i = jnp.take_along_axis(d, i[..., None], axis=-1)[..., 0]
        mu_i = jnp.take_along_axis(min_units, i[..., None], axis=-1)[..., 0]
        take = (leftover < 0) & (d_i > mu_i)
        d = d - ((idx == i[..., None]) & take[..., None]).astype(it)
        return d, leftover + take.astype(it), kk + 1

    kk0 = jnp.zeros(leftover.shape, it)
    d, leftover, kk = lax.while_loop(tb_cond, tb_body, (d, leftover, kk0))
    trips_tb = jnp.max(kk).astype(jnp.int32)  # every lane steps each trip

    # -- threshold-count bulk grant (static branch: skipped entirely when no
    #    lane is monotone) — collapses all but the boundary-tied units into
    #    one more bisection; fast_mask routes it per lane.
    rem = alloc - jnp.floor(alloc)
    trips_thr = jnp.zeros(2, jnp.int32)
    if completion_fast:
        d, leftover, trips_thr = _threshold_prefill(
            segs, caps_i, d, leftover, t_star, rel_tol, max_steps, fast_mask,
        )

    # -- greedy completion (see _complete_greedy_one); stacked banks flatten
    #    their leading dims and vmap, so every column completes in the same
    #    device program (lanes mask out as their leftovers hit zero).
    batch = xs.shape[:-2]
    if batch:
        b = int(np.prod(batch))
        p_dim, k_dim = xs.shape[-2], xs.shape[-1]
        d, ok, trips_gr = jax.vmap(_complete_greedy_one)(
            xs.reshape(b, p_dim, k_dim),
            ss.reshape(b, p_dim, k_dim),
            counts.reshape(b, p_dim),
            caps_i.reshape(b, p_dim),
            d.reshape(b, p_dim),
            rem.reshape(b, p_dim),
            leftover.reshape(b),
        )
        d = d.reshape(*batch, p_dim)
        ok = ok.reshape(batch)
        trips_gr = trips_gr.sum(dtype=jnp.int32)
    else:
        d, ok, trips_gr = _complete_greedy_one(xs, ss, counts, caps_i, d, rem, leftover)
    trips = jnp.concatenate([trips_cont, trips_tb[None], trips_thr, trips_gr[None]])
    return d, ok, t_star, trips


_partition_units_jit = partial(
    jax.jit, static_argnames=("max_steps", "completion_fast")
)(_partition_units_impl)


# ---------------------------------------------------------------------------
# Hierarchical inner solves: one device program over [g, p_max, k] group
# blocks, with SIZE-ROUTED execution (``Hierarchy._inner_serial``).  Small
# groups run BATCHED (one [g, ...] bisection — every loop update is already
# masked per lane, so results are bit-identical to solo runs); past a few MB
# a group, lax.map runs the groups SEQUENTIALLY, each group's [p_g, k] block
# through its whole t* bisection.  Either way the program compiles once and
# dispatches once.  Under shard_map the same body runs per device over its
# local group lanes (no collectives: every group's solve is independent), so
# no single device ever touches more than its ceil(g/ndev) blocks of the
# bank.
# ---------------------------------------------------------------------------


def _hier_inner_map(
    xs, ss, counts, caps_i, n, min_units, fast_mask, *,
    rel_tol, max_steps, completion_fast, serial=True,
):
    """Per-group integer partitions: ``xs``/``ss`` are ``[g, p_max, k]``
    (members right-padded with caps=0 / min_units=0 rows), ``n`` ``[g]`` the
    outer solve's group shares, ``min_units`` ``[g, p_max]``, ``fast_mask``
    ``[g]`` the per-group completion routing.  ``serial`` picks lax.map
    (one group at a time, for large groups) over the batched solve (one
    masked bisection, for small groups) — the two return BIT-IDENTICAL
    allocations, see the routing note above.  Returns
    ``(d [g, p_max], ok [g], t_star [g])``."""
    if not serial:
        return _partition_units_impl(
            xs, ss, counts, caps_i, n, min_units,
            jnp.asarray(rel_tol, xs.dtype), max_steps, fast_mask,
            completion_fast=completion_fast,
        )[:3]

    def body(args):
        xs_g, ss_g, counts_g, caps_g, n_g, mu_g, fm_g = args
        return _partition_units_impl(
            xs_g, ss_g, counts_g, caps_g, n_g, mu_g,
            jnp.asarray(rel_tol, xs_g.dtype), max_steps, fm_g,
            completion_fast=completion_fast,
        )[:3]

    return lax.map(body, (xs, ss, counts, caps_i, n, min_units, fast_mask))


_hier_inner_jit = partial(
    jax.jit, static_argnames=("rel_tol", "max_steps", "completion_fast", "serial")
)(_hier_inner_map)


@jax.jit
def _gather_blocks_jit(xs, ss, counts, idx, mask):
    """The ``[g, p_max, k]`` member blocks of a ``[p, k]`` bank for member
    indices ``idx[g, p_max]``; padded rows (``mask`` False) are zeros with
    count 0."""
    zero = jnp.zeros((), xs.dtype)
    m3 = mask[..., None]
    return (
        jnp.where(m3, xs[idx], zero),
        jnp.where(m3, ss[idx], zero),
        jnp.where(mask, counts[idx], jnp.zeros((), counts.dtype)),
    )


def _fold_in_impl(xs, ss, counts, x, s, valid):
    """Vectorized sorted insert of one ``(x_i, s_i)`` observation per row.

    Exactly ``PiecewiseLinearFPM.add_point`` semantics, for all rows at once:
    replace the speed on an exact duplicate ``x``, otherwise shift-insert at
    the bisect position and re-pad with the row's (possibly new) last point.
    Rows with ``valid[i] == False`` are untouched.
    """
    k = xs.shape[-1]
    j = jnp.arange(k)
    in_prefix = j < counts[..., None]
    dup = in_prefix & (xs == x[..., None])
    has_dup = jnp.any(dup, axis=-1)
    do_replace = valid & has_dup
    do_insert = valid & ~has_dup

    ss = jnp.where(dup & do_replace[..., None], s[..., None], ss)

    pos = jnp.sum(in_prefix & (xs < x[..., None]), axis=-1)
    jm1 = jnp.maximum(j - 1, 0)
    xs_prev, ss_prev = xs[..., jm1], ss[..., jm1]
    at = j == pos[..., None]
    before = j < pos[..., None]
    xs_ins = jnp.where(before, xs, jnp.where(at, x[..., None], xs_prev))
    ss_ins = jnp.where(before, ss, jnp.where(at, s[..., None], ss_prev))
    new_counts = counts + do_insert.astype(counts.dtype)
    last = jnp.maximum(new_counts - 1, 0)
    last_x = jnp.take_along_axis(xs_ins, last[..., None], axis=-1)
    last_s = jnp.take_along_axis(ss_ins, last[..., None], axis=-1)
    pad = j >= new_counts[..., None]
    xs_ins = jnp.where(pad, last_x, xs_ins)
    ss_ins = jnp.where(pad, last_s, ss_ins)

    ins = do_insert[..., None]
    return (
        jnp.where(ins, xs_ins, xs),
        jnp.where(ins, ss_ins, ss),
        new_counts,
    )


# The carry (xs, ss, counts) is donated: every backend reuses its buffers
# for the output, and the bank that was folded is spent.
_fold_in_jit = partial(jax.jit, donate_argnums=(0, 1, 2))(_fold_in_impl)
# Non-donating twin: double-buffered callers (the fleet's pipelined rounds)
# fold into a NEW carry while the previous generation's buffers stay valid,
# so an in-flight repartition can keep reading them.  Keeping separate jit
# caches means a pipelined fleet never perturbs the donating path's
# recompile accounting.
_fold_in_nodonate_jit = jax.jit(_fold_in_impl)


# ---------------------------------------------------------------------------
# The bank
# ---------------------------------------------------------------------------


@dataclass
class JaxModelBank:
    """Device-resident padded FPM bank; accepts leading batch dims
    (``[p, k]`` for one fleet, ``[q, p, k]`` for a stacked 2-D grid).

    ``max_count`` (host-side upper bound on ``counts.max()``) and
    ``empty_rows`` (host-side ``counts == 0`` mirror) keep the hot paths —
    fold-in growth checks and per-repartition feasibility validation — free
    of blocking device->host syncs; ``None`` means unknown (computed and
    cached on first use).
    """

    xs: jnp.ndarray
    ss: jnp.ndarray
    counts: jnp.ndarray
    max_count: Optional[int] = None
    empty_rows: Optional[np.ndarray] = None
    # Host-side monotone-time flag (None = unknown; resolved by is_monotone()
    # — from the numpy bank's host check at construction, or by one tiny
    # jitted reduction + scalar sync after a device-side fold_in).  Routes
    # the threshold-count completion.
    monotone: Optional[bool] = None
    # Per-lane mirror for stacked [q, p, k] banks (None = unknown; resolved
    # by monotone_lanes()): routes the completion per column, so one
    # adversarial column demotes only itself while the rest keep the
    # threshold-count bulk grant — in the same device program.
    monotone_cols: Optional[np.ndarray] = None
    # Optional energy sub-bank (same layout; ss holds energy RATES x/E(x),
    # so energy.time(x) == E(x)) — see the "time and energy" section in
    # modelbank.py and core/energy.py.
    energy: Optional["JaxModelBank"] = None
    # Fold-in generation tag (host int): construction paths start at 0 and
    # every ``fold_in`` returns a bank one generation newer.  Double-buffered
    # consumers (the fleet's pipelined rounds) use the tag to bound how
    # stale a carry a repartition may read — never more than
    # ``pipeline_depth`` fold generations behind the newest.
    generation: int = 0

    is_jax = True  # duck-type marker for the partition.py dispatcher

    # -- construction --------------------------------------------------------

    @classmethod
    def from_bank(cls, bank: ModelBank, dtype=None) -> "JaxModelBank":
        """Device copy of a numpy bank.  ``dtype`` overrides the float dtype
        of the model arrays (the ``SpeedStore`` dtype policy — e.g.
        ``np.float32`` for a cheaper serving-fleet bank); the default keeps
        the platform-native dtype (float64 under x64)."""
        return cls(
            xs=jnp.asarray(bank.xs, dtype=dtype),
            ss=jnp.asarray(bank.ss, dtype=dtype),
            counts=jnp.asarray(bank.counts),
            max_count=int(bank.counts.max(initial=0)),
            empty_rows=np.asarray(bank.counts) == 0,
            # resolve on the host while the arrays are still numpy — one
            # O(p k) pass, so stacked/2-D paths never pay a device check
            monotone=bank.is_monotone(),
            energy=(
                cls.from_bank(bank.energy, dtype=dtype)
                if bank.energy is not None
                else None
            ),
        )

    @classmethod
    def from_models(cls, models: Sequence[object], dtype=None) -> "JaxModelBank":
        """Adapt scalar models (``TypeError`` for non-piecewise ones —
        callers fall back to the host paths)."""
        return cls.from_bank(ModelBank.from_models(models), dtype=dtype)

    @classmethod
    def empty(cls, p: int, k: int = 8, dtype=None) -> "JaxModelBank":
        """A bank of ``p`` empty rows (the cold-start DFPA carry)."""
        return cls(
            xs=jnp.zeros((p, k), dtype=dtype),
            ss=jnp.zeros((p, k), dtype=dtype),
            counts=jnp.zeros((p,), dtype=jax.dtypes.canonicalize_dtype(np.int64)),
            max_count=0,
            empty_rows=np.ones((p,), dtype=bool),
            monotone=True,  # vacuous: no observed points yet
        )

    @classmethod
    def stack(
        cls, banks: Sequence["JaxModelBank"], min_k: Optional[int] = None
    ) -> "JaxModelBank":
        """Stack ``q`` same-``p`` banks into one ``[q, p, k]`` bank so every
        column's ``t*`` bisects simultaneously (the 2-D partitioner).

        ``min_k`` reserves padded knot capacity up front: a serving fleet
        that restacks with a fixed ``min_k`` keeps the carry's shapes — and
        therefore its compiled programs — identical across sessions, and
        ``fold_in`` never pays a growth recompile until a row actually
        exceeds the reservation."""
        k = max(int(b.xs.shape[-1]) for b in banks)
        if min_k is not None:
            k = max(k, int(min_k))
        padded = [b._padded_to(k) for b in banks]
        flags = [b.monotone for b in banks]
        energy = (
            cls.stack([b.energy for b in banks], min_k=min_k)
            if banks and all(b.energy is not None for b in banks)
            else None
        )
        return cls(
            energy=energy,
            xs=jnp.stack([px for px, _ in padded]),
            ss=jnp.stack([ps for _, ps in padded]),
            counts=jnp.stack([b.counts for b in banks]),
            max_count=max(b._max_count_bound() for b in banks),
            empty_rows=np.stack([b._empty_rows_host() for b in banks]),
            # All columns known-monotone -> stacked fast path; any known
            # violation demotes its own column (per-lane routing); unknowns
            # resolve lazily on first partition.
            monotone=(
                True if all(f is True for f in flags)
                else False if any(f is False for f in flags)
                else None
            ),
            monotone_cols=(
                np.asarray(flags, dtype=bool)
                if all(f is not None for f in flags)
                else None
            ),
        )

    def _padded_to(self, k: int):
        extra = k - int(self.xs.shape[-1])
        if extra <= 0:
            return self.xs, self.ss
        # padding repeats the last column (== the row's last point, or the
        # zeros of an empty row) — same convention as from_point_lists.
        # Done on the host: the source width varies bank to bank, and device
        # repeat/concatenate would compile a fresh (k_src -> k) program for
        # every width seen; a [p, k] pad is host-trivial and jnp.asarray is
        # a transfer, not a trace.
        xs = np.asarray(self.xs)
        ss = np.asarray(self.ss)
        return (
            jnp.asarray(np.concatenate([xs, np.repeat(xs[..., -1:], extra, axis=-1)], axis=-1)),
            jnp.asarray(np.concatenate([ss, np.repeat(ss[..., -1:], extra, axis=-1)], axis=-1)),
        )

    def to_bank(self) -> ModelBank:
        """Host snapshot as the numpy :class:`ModelBank` (single bank only)."""
        if self.xs.ndim != 2:
            raise ValueError("to_bank() requires an unbatched [p, k] bank")
        return ModelBank(
            xs=np.asarray(self.xs, dtype=np.float64),
            ss=np.asarray(self.ss, dtype=np.float64),
            counts=np.asarray(self.counts, dtype=np.int64),
            monotone=self.monotone,
            energy=self.energy.to_bank() if self.energy is not None else None,
        )

    # -- shape ---------------------------------------------------------------

    @property
    def p(self) -> int:
        return int(self.xs.shape[-2])

    def __len__(self) -> int:
        return self.p

    @property
    def dtype(self):
        return self.xs.dtype

    # -- batched evaluation (device) -----------------------------------------

    def speed(self, x) -> jnp.ndarray:
        x = jnp.broadcast_to(jnp.asarray(x, self.dtype), self.counts.shape)
        return _speed(self.xs, self.ss, self.counts, x)

    def time(self, x) -> jnp.ndarray:
        x = jnp.broadcast_to(jnp.asarray(x, self.dtype), self.counts.shape)
        return _time(self.xs, self.ss, self.counts, x)

    def alloc_at_time(self, t, caps) -> jnp.ndarray:
        caps = jnp.broadcast_to(jnp.asarray(caps, self.dtype), self.counts.shape)
        return _alloc_at_time(self.xs, self.ss, self.counts, t, caps)

    def total_alloc(self, t, caps) -> jnp.ndarray:
        return self.alloc_at_time(t, caps).sum(axis=-1)

    def scaled(self, speed_scale) -> "JaxModelBank":
        """New bank with every row's speeds scaled (2-D column-width rescale).

        ``fold_in`` donates its carry, so the ``xs``/``counts`` buffers are
        copied: folding either bank cannot invalidate the other.
        """
        scale_host = np.asarray(speed_scale, dtype=np.float64)
        scale = jnp.broadcast_to(jnp.asarray(speed_scale, self.dtype), self.counts.shape)
        positive = bool(np.all(scale_host > 0.0))
        return JaxModelBank(
            xs=jnp.array(self.xs), ss=self.ss * scale[..., None],
            counts=jnp.array(self.counts),
            max_count=self.max_count, empty_rows=self.empty_rows,
            # positive per-row scaling preserves time-monotonicity
            monotone=self.monotone if positive else None,
            monotone_cols=self.monotone_cols if positive else None,
            generation=self.generation,
            energy=self.energy,  # problem-size semantics unchanged
        )

    def copy(self) -> "JaxModelBank":
        """Deep copy of the device buffers.  Needed by holders of a snapshot:
        ``fold_in`` donates its carry, so the next fold invalidates the
        original buffers."""
        return JaxModelBank(
            xs=jnp.array(self.xs), ss=jnp.array(self.ss),
            counts=jnp.array(self.counts), max_count=self.max_count,
            empty_rows=self.empty_rows, monotone=self.monotone,
            monotone_cols=self.monotone_cols, generation=self.generation,
            energy=self.energy.copy() if self.energy is not None else None,
        )

    # -- the energy sub-bank (core/energy.py) --------------------------------

    def with_energy(self, energy: "JaxModelBank") -> "JaxModelBank":
        """Attach an energy sub-bank (same shape; ``ss`` holds energy rates
        ``x / E(x)``) — returns a new bank sharing this bank's buffers."""
        if energy.counts.shape != self.counts.shape:
            raise ValueError(
                f"energy bank shape {energy.counts.shape} != speed bank "
                f"shape {self.counts.shape}"
            )
        return JaxModelBank(
            xs=self.xs, ss=self.ss, counts=self.counts,
            max_count=self.max_count, empty_rows=self.empty_rows,
            monotone=self.monotone, monotone_cols=self.monotone_cols,
            generation=self.generation, energy=energy,
        )

    def energy_at(self, d) -> jnp.ndarray:
        """Per-processor energies ``E_i(d_i)`` of a distribution (0 for
        ``d_i <= 0``, NaN on empty energy rows with units)."""
        if self.energy is None:
            raise ValueError("no energy sub-bank attached (use with_energy)")
        return self.energy.time(d)

    def fleet_energy(self, d) -> float:
        """Total fleet energy ``sum_i E_i(d_i)`` of a distribution (host
        scalar; one reduction + sync)."""
        return float(self.energy_at(d).sum())

    def _max_count_bound(self) -> int:
        """Host-side upper bound on ``counts.max()`` (syncs once if unknown,
        then stays host-tracked)."""
        if self.max_count is None:
            self.max_count = int(np.asarray(self.counts).max(initial=0))
        return self.max_count

    def _empty_rows_host(self) -> np.ndarray:
        """Host-side ``counts == 0`` mirror (syncs once if unknown, then
        maintained by ``fold_in`` without further transfers)."""
        if self.empty_rows is None:
            self.empty_rows = np.asarray(self.counts) == 0
        return self.empty_rows

    def is_monotone(self) -> bool:
        """Host bool of the bank's monotone-time flag (the threshold-count
        completion's routing contract — see ``ModelBank.is_monotone``).

        Construction paths inherit the numpy bank's host check for free;
        after a device-side ``fold_in`` the flag is unknown and resolving it
        costs one ``O(p k)`` jitted reduction plus a scalar device->host
        sync — paid at most once per fold/partition cycle, i.e. amortized
        into the repartition the observation was folded in for."""
        if self.monotone is None:
            if self.monotone_cols is not None:
                self.monotone = bool(np.all(self.monotone_cols))
            else:
                self.monotone = bool(
                    np.all(_monotone_lanes_jit(self.xs, self.ss, self.counts))
                )
        return self.monotone

    def monotone_lanes(self) -> np.ndarray:
        """Per-lane host mirror of :meth:`is_monotone` — one bool per
        leading batch element (shape ``[q]`` for a stacked bank, ``()`` for
        a plain one).  ``completion="auto"`` on a stacked bank routes the
        threshold-count completion through this, so a single non-monotone
        column demotes only its own lane to the exact per-unit loop while
        every other column keeps the bulk grant (one device program either
        way).  Same lazy-resolution contract as the scalar flag."""
        shape = self.counts.shape[:-1]
        if self.monotone_cols is None:
            if self.monotone is True:
                # the scalar flag is the AND of the lanes, so only True
                # determines them all; False means *some* lane violates.
                self.monotone_cols = np.ones(shape, dtype=bool)
            else:
                self.monotone_cols = np.asarray(
                    _monotone_lanes_jit(self.xs, self.ss, self.counts)
                ).reshape(shape)
        return self.monotone_cols

    # -- the jitted partitioners --------------------------------------------

    def _check_feasible(self, caps: np.ndarray, n) -> None:
        if np.any((caps > 0.0) & self._empty_rows_host()):
            raise ValueError("empty FPM")

    def partition_continuous(
        self, n, caps=None, *, rel_tol: float = 1e-12, max_steps: int = 200
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Continuous optimal partition on device; ``n`` may be batched for a
        stacked bank.  Returns ``(allocations, t_star)`` as jnp arrays."""
        shape = self.counts.shape
        # Caps are validated host-side first, then uploaded ONCE — the hot
        # repartition path never reads device memory back.
        if caps is not None:
            caps_host = np.broadcast_to(np.asarray(caps, dtype=np.float64), shape)
        else:
            caps_host = np.broadcast_to(
                np.asarray(n, dtype=np.float64)[..., None], shape
            )
        self._check_feasible(caps_host, n)
        return _partition_continuous_jit(
            self.xs, self.ss, self.counts,
            jnp.asarray(caps_host, self.dtype),
            jnp.asarray(n, self.dtype),
            jnp.asarray(rel_tol, self.dtype),
            max_steps,
        )[:2]

    def partition_units(
        self, n, caps=None, *, min_units=0, max_steps: int = 200,
        with_t: bool = False, completion: str = "auto",
        completion_lanes=None, defer: bool = False,
    ) -> np.ndarray:
        """Integer partition on device; host-side feasibility checks raise
        the same ``ValueError`` s as the scalar and numpy-bank paths.

        ``n`` is a scalar (or ``[q]`` for a stacked bank, partitioning every
        column simultaneously); ``min_units`` may likewise be per-column on a
        stacked bank.  Returns the host ``int`` allocation array; with
        ``with_t=True`` returns ``(allocations, t_star)`` — the inner
        continuous solve's equal-time point, at zero extra device work.

        ``completion`` routes the integer completion (see the "completion
        modes" section in ``modelbank.py``): ``"auto"`` uses the
        threshold-count bulk grant iff the bank is monotone-time (one extra
        jitted bisection instead of ~p/2 sequential argmin iterations —
        the p=10^5 millisecond-repartition path), ``"greedy"`` forces the
        exact per-unit loop, ``"threshold"`` forces the bulk grant
        (benchmark-only on non-monotone banks).  On a stacked bank ``"auto"``
        routes *per column* (``monotone_lanes``), so an adversarial column
        demotes only itself; ``completion_lanes`` (a ``[q]`` bool mask, used
        by the fleet scheduler) overrides the routing explicitly — True
        lanes take the bulk grant, False lanes the exact loop — keeping
        mixed-mode fleets in one device program.

        ``defer=True`` dispatches the device program and returns WITHOUT
        blocking: the result is a ``(d, ok)`` pair of device arrays (JAX
        async dispatch keeps computing in the background) to be materialized
        later with :func:`fetch_partition` — which performs the same
        integer-completion feasibility raise this call would have.  The
        pipelined fleet round uses this to overlap next round's repartition
        with the in-flight fold and the host-side bookkeeping between them.
        """
        if completion not in ("auto", "threshold", "greedy"):
            raise ValueError(f"unknown completion mode {completion!r}")
        shape = self.counts.shape
        p = shape[-1]
        if completion_lanes is not None:
            lanes_host = np.array(
                np.broadcast_to(np.asarray(completion_lanes, dtype=bool), shape[:-1])
            )
        elif completion == "threshold":
            lanes_host = np.ones(shape[:-1], dtype=bool)
        elif completion == "greedy":
            lanes_host = np.zeros(shape[:-1], dtype=bool)
        elif self.counts.ndim >= 2:
            lanes_host = self.monotone_lanes()  # per-column auto routing
        else:
            lanes_host = np.full(shape[:-1], self.is_monotone(), dtype=bool)
        fast = bool(np.any(lanes_host))
        n_host = np.broadcast_to(np.asarray(n), shape[:-1])
        if np.any(n_host < 0):
            raise ValueError("n must be non-negative")
        mu_host = np.broadcast_to(np.asarray(min_units, dtype=np.int64), shape[:-1])
        if np.any(mu_host * p > n_host):
            i = int(np.argmax(np.reshape(mu_host * p > n_host, (-1,))))
            raise ValueError(
                f"min_units={int(np.reshape(mu_host, (-1,))[i])} infeasible for "
                f"n={int(np.reshape(n_host, (-1,))[i])}, p={p}"
            )
        idtype = self.counts.dtype
        # Host-side caps first (validation below), one device upload after —
        # no blocking device->host round-trips on the repartition hot path.
        if caps is None:
            caps_host = np.broadcast_to(
                np.asarray(n_host, dtype=np.int64)[..., None], shape
            )
        else:
            caps_host = np.broadcast_to(np.asarray(caps, dtype=np.int64), shape)
        under = (caps_host < mu_host[..., None]) & (mu_host[..., None] > 0)
        if np.any(under):
            i = int(np.argmax(np.reshape(under, (-1,))))
            raise ValueError(
                f"min_units={int(np.reshape(mu_host, (-1,))[i // p])} "
                f"infeasible: cap {int(caps_host.reshape(-1)[i])} < min_units"
            )
        clipped = np.minimum(caps_host.astype(np.float64), n_host[..., None].astype(np.float64))
        short = clipped.sum(axis=-1) < n_host
        if np.any(short):
            i = int(np.argmax(np.reshape(short, (-1,))))
            raise ValueError(
                f"infeasible: sum(caps)={float(clipped.reshape(-1, p)[i].sum())} "
                f"< n={float(np.reshape(n_host, (-1,))[i])}"
            )
        self._check_feasible(caps_host.astype(np.float64), n)
        # min_units broadcast to row shape [..., p]: the kernel takes per-row
        # floors (uniform here; genuinely per-row on the hierarchical path).
        args = (
            self.xs, self.ss, self.counts,
            jnp.asarray(caps_host, idtype),
            jnp.asarray(n_host),
            jnp.asarray(np.broadcast_to(mu_host[..., None], shape), idtype),
            jnp.asarray(1e-12, self.dtype),
            max_steps,
            jnp.asarray(lanes_host),
        )
        if defer:
            d, ok, t_star, _ = _partition_units_jit(*args, completion_fast=fast)
            return (d, ok, t_star) if with_t else (d, ok)
        # the wait: dispatch until the program's results are on the host
        tel = _obs_active()
        rec = tel is not None and tel.enabled
        if rec:
            tok = tel.begin("modelbank.partition.wait")
        d, ok, t_star, trips = _partition_units_jit(*args, completion_fast=fast)
        if rec:
            trips.copy_to_host_async()  # on the host with d: no extra round trip
        ok_host = bool(np.all(np.asarray(ok)))
        d_host = np.asarray(d)
        t_host = np.asarray(t_star) if with_t else None
        if rec:
            tel.end(tok)
            for loop, v in zip(TRIP_LOOPS, np.asarray(trips).tolist()):
                tel.counter(f"partition.trips.{loop}", v)
        if not ok_host:
            raise ValueError("caps infeasible during integer completion")
        return (d_host, t_host) if with_t else d_host

    # -- device-resident observation fold-in ---------------------------------

    def fold_in(self, x, s, valid=None, *, donate: bool = True) -> "JaxModelBank":
        """Insert one observation ``(x_i, s_i)`` per row (vectorized sorted
        insert; duplicate ``x`` replaces the speed).  Returns the updated
        bank; the old buffers are donated to it, so this bank is spent.
        Grows the padded width (by doubling) when any row is full.

        ``donate=False`` routes through a non-donating twin of the fold
        kernel so THIS bank's buffers stay valid after the call — the
        double-buffer contract pipelined fleet rounds rely on (the previous
        generation keeps serving an in-flight repartition while the new one
        folds).  The returned bank is tagged one :attr:`generation` newer
        either way."""
        # the host side of a fold: uploads, width check, kernel dispatch
        tel = _obs_active()
        rec = tel is not None and tel.enabled
        if rec:
            tok = tel.begin("modelbank.fold_in")
        x = jnp.broadcast_to(jnp.asarray(x, self.dtype), self.counts.shape)
        s = jnp.broadcast_to(jnp.asarray(s, self.dtype), self.counts.shape)
        # valid is host data in every caller (DFPA / BalanceController build
        # Python lists); mirror it on the host so empty_rows stays host-
        # tracked, then upload.
        if valid is None:
            valid_host = np.ones(self.counts.shape, dtype=bool)
        else:
            valid_host = np.broadcast_to(np.asarray(valid, bool), self.counts.shape)
        valid = jnp.asarray(valid_host)
        xs, ss = self.xs, self.ss
        k = int(xs.shape[-1])
        bound = self._max_count_bound()
        if bound >= k:
            # The host-tracked bound overcounts duplicate-x folds (they
            # replace a speed without growing counts), so before paying for
            # a width doubling — new shape, new jit traces — resync the true
            # maximum (a [p]-int transfer, at most once per k folds).  A
            # steady-state carry re-observing the same distribution keeps
            # its width (and its compiled kernels) forever.
            bound = int(np.asarray(self.counts).max(initial=0))
            self.max_count = bound
            if bound >= k:
                k = max(2 * k, 1)
                xs, ss = self._padded_to(k)
        kernel = _fold_in_jit if donate else _fold_in_nodonate_jit
        nxs, nss, ncounts = kernel(xs, ss, self.counts, x, s, valid)
        if rec:
            tel.end(tok)
        return JaxModelBank(
            xs=nxs, ss=nss, counts=ncounts, max_count=min(bound + 1, k),
            generation=self.generation + 1,
            empty_rows=self._empty_rows_host() & ~valid_host,
            # The inserted points can create OR (duplicate-x replace) remove
            # a monotonicity violation; the flag is re-resolved lazily by
            # is_monotone() on the next partition (one device reduction).
            monotone=None,
            # Speed observations don't touch the energy sub-bank; fold
            # energy observations into it directly (it is a bank).
            energy=self.energy,
        )


def fetch_partition(deferred) -> np.ndarray:
    """Materialize a ``partition_units(..., defer=True)`` result: blocks on
    the in-flight device program, runs the integer-completion feasibility
    check the eager call would have run, and returns the host allocation
    array (plus ``t_star`` when the deferred call used ``with_t=True``)."""
    d, ok = deferred[0], deferred[1]
    d_host = np.asarray(d)
    if not bool(np.all(np.asarray(ok))):
        raise ValueError("caps infeasible during integer completion")
    if len(deferred) == 3:
        return d_host, np.asarray(deferred[2])
    return d_host
