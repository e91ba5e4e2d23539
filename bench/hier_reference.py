"""The plain reference of the two-level partition, for the cells whose
scheduler has ``groups``.

It imports nothing of the program.  From estimates the reference built
itself (``bench/reference.py``'s ``Estimates``) and a group of each
processor, it computes the partition that a two-level scheduler must serve:

* **aggregate**: a group's problem size at time ``t`` is the sum of its
  members' ``max{x <= cap : x / s(x) <= t}``.  It is sampled at times drawn
  from the members' knots: the distinct finite positive knot times
  ``x / s`` of members with a positive cap and each such member's time at
  its cap; above ``quota = max(max_knots // 4, 2)`` of them, the ``quota``
  at the rounded positions of ``linspace(0, count - 1, quota)``; each kept
  time joined by ``t (1 - 1e-9)``; and, where the times span an interval,
  ``geomspace(first, last, quota)``.  A sample is a knot of the group's
  speed function where its size is positive and larger than at the
  previous sample; the knot's speed is size over time;
* **outer solve**: ``bench/reference.py``'s continuous solve over the group
  functions with each group's summed caps; each group's share is its floor,
  at least ``min_units`` times its size and at most its caps; an overshoot is
  taken back from the groups of largest time per unit, round-robin; each
  leftover unit goes to the group with the smallest ``(time(share + 1),
  -fractional remainder, index)``;
* **inner solves**: each group's share is partitioned over its members as
  the flat partition does it (``bench/reference.py``'s docstring), the
  continuous solve run for all groups at once, with each member's cap
  clipped at its group's share.

Everything is float64 numpy or plain Python.
"""

from __future__ import annotations

import heapq
from typing import List, Sequence, Tuple

import numpy as np

from . import reference as ref


def members_of(groups: Sequence[int]) -> List[np.ndarray]:
    """Each group's processors, ascending, groups by ascending id."""
    garr = np.asarray(groups)
    return [np.flatnonzero(garr == g) for g in np.unique(garr)]


def alloc_rows(xs, ss, counts, t: np.ndarray, caps: np.ndarray) -> np.ndarray:
    """``max{x in [0, cap_i] : x / s_i(x) <= t_i}`` for every row ``i``, a
    time per row (``bench/reference.py``'s ``alloc_at_time`` with ``t`` a
    vector)."""
    t = np.asarray(t, dtype=np.float64)
    rows = np.arange(xs.shape[0])
    last = np.maximum(counts - 1, 0)
    first_x, first_s = xs[:, 0], ss[:, 0]
    last_x, last_s = xs[rows, last], ss[rows, last]
    best = np.minimum(t * first_s, np.minimum(first_x, caps))
    k = xs.shape[1]
    if k >= 2:
        x0, x1 = xs[:, :-1], xs[:, 1:]
        s0, s1 = ss[:, :-1], ss[:, 1:]
        seg = np.arange(k - 1)[None, :] < (counts - 1)[:, None]
        seg &= (x0 < caps[:, None]) & (x1 > x0)
        x1c = np.minimum(x1, caps[:, None])
        tc = t[:, None]
        with np.errstate(divide="ignore", invalid="ignore"):
            m = (s1 - s0) / np.where(x1 > x0, x1 - x0, 1.0)
            a = 1.0 - tc * m
            b = tc * (s0 - m * x0)
            ub = b / np.where(a != 0.0, a, 1.0)
        cand = np.where(
            a > 0.0,
            np.where(ub >= x0, np.minimum(ub, x1c), 0.0),
            np.where(a == 0.0, np.where(b >= 0.0, x1c, 0.0),
                     np.where(x1c >= ub, x1c, 0.0)),
        )
        best = np.maximum(best, np.where(seg, cand, 0.0).max(axis=1))
    ub_r = t * last_s
    right = (caps > last_x) & (ub_r >= last_x)
    best = np.maximum(best, np.where(right, np.minimum(ub_r, caps), 0.0))
    best = np.where((caps > 0.0) & (counts > 0), best, 0.0)
    return np.where(t > 0.0, best, 0.0)


# -- aggregation --------------------------------------------------------------


def sample_times(xs, ss, counts, caps, max_knots: int) -> np.ndarray:
    """One group's sample times (rows are its members)."""
    k = xs.shape[1]
    valid = (np.arange(k)[None, :] < counts[:, None]) & (caps[:, None] > 0)
    with np.errstate(divide="ignore", invalid="ignore"):
        t_pts = np.where(ss > 0, xs / ss, np.nan)
    ts = list(t_pts[valid])
    active = (caps > 0) & (counts > 0)
    if np.any(active):
        with np.errstate(invalid="ignore"):  # an infinite cap times out at infinity
            at_cap = ref.times_at(xs, ss, counts, np.where(active, caps, 1.0))
        ts += list(at_cap[active])
    ts = np.asarray(ts, dtype=np.float64)
    ts = np.unique(ts[np.isfinite(ts) & (ts > 0)])
    if ts.size == 0:
        return ts
    quota = max(max_knots // 4, 2)
    if ts.size > quota:
        ts = ts[np.unique(np.round(np.linspace(0, ts.size - 1, quota)).astype(int))]
    ts = np.unique(np.concatenate([ts, ts * (1.0 - 1e-9)]))
    if ts[-1] > ts[0]:
        ts = np.unique(np.concatenate([ts, np.geomspace(ts[0], ts[-1], quota)]))
    return ts


def aggregate(xs, ss, counts, members, caps, max_knots: int):
    """The groups' speed functions as a padded ``(xs, ss, counts)`` bank."""
    times = [sample_times(xs[m], ss[m], counts[m], caps[m], max_knots) for m in members]
    width = max((t.size for t in times), default=0)
    if width == 0:
        g = len(members)
        return np.zeros((g, 1)), np.zeros((g, 1)), np.zeros(g, dtype=np.int64)
    # every member evaluated at its group's times, one time column at a time
    tmat = np.ones((xs.shape[0], width))
    for m, t in zip(members, times):
        if t.size:
            tmat[m, : t.size] = t
            tmat[m, t.size:] = t[-1]
    allocs = np.stack(
        [alloc_rows(xs, ss, counts, tmat[:, j], caps) for j in range(width)], axis=1
    )
    pts = []
    for m, t in zip(members, times):
        size = allocs[m, : t.size].sum(axis=0)
        keep = (size > 0) & np.concatenate([[True], np.diff(size) > 0])[: size.size]
        pts.append((size[keep], size[keep] / t[keep]))
    est = ref.Estimates(len(members))
    for row, (px, ps) in zip(est.pts, pts):
        row.update(zip(px.tolist(), ps.tolist()))
    return est.padded()


# -- the outer and inner solves -------------------------------------------------


def complete(xs, ss, counts, x, d, caps, floors, n: int) -> np.ndarray:
    """Integer allocations from the continuous ``x`` and the floored ``d``:
    take back an overshoot, then grant the leftover units one by one."""
    d = np.array(d, dtype=np.int64)
    leftover = int(n - d.sum())
    if leftover < 0:
        with np.errstate(invalid="ignore"):
            per_unit = ref.times_at(xs, ss, counts, d.astype(np.float64)) / np.maximum(d, 1)
        order = sorted(range(len(d)), key=lambda i: per_unit[i], reverse=True)
        k = 0
        while leftover < 0:
            i = order[k % len(d)]
            if d[i] > floors[i]:
                d[i] -= 1
                leftover += 1
            k += 1
    rem = x - np.floor(x)
    if leftover > 0:
        t_next = ref.times_at(xs, ss, counts, (d + 1).astype(np.float64))
        heap = [(t_next[i], -rem[i], i) for i in range(len(d)) if d[i] + 1 <= caps[i]]
        heapq.heapify(heap)
        while leftover > 0:
            if not heap:
                raise ValueError("caps infeasible")
            _, negrem, i = heapq.heappop(heap)
            d[i] += 1
            leftover -= 1
            if d[i] + 1 <= caps[i]:
                c = int(counts[i])
                x1 = float(d[i] + 1)
                heapq.heappush(heap, (x1 / ref.speed_at(xs[i, :c].tolist(), ss[i, :c].tolist(), x1),
                                      negrem, i))
    return d


def inner_continuous(xs, ss, counts, members, shares, caps) -> np.ndarray:
    """Every group's continuous partition of its share, all groups at once:
    per group a doubling bracket and a bisection to ``hi - lo <= 1e-12 hi``,
    then the allocations at ``hi`` scaled down by their excess."""
    g = len(members)
    p_max = max((len(m) for m in members), default=1)
    idx = np.zeros((g, p_max), dtype=np.int64)
    on = np.zeros((g, p_max), dtype=bool)
    for r, m in enumerate(members):
        idx[r, : len(m)] = m
        on[r, : len(m)] = True
    n = np.asarray(shares, dtype=np.float64)
    cap = np.where(on, np.minimum(caps[idx].astype(np.float64), n[:, None]), 0.0)
    bx, bs = xs[idx].reshape(g * p_max, -1), ss[idx].reshape(g * p_max, -1)
    bc = np.where(on, counts[idx], 0).reshape(-1)
    flat_cap = cap.reshape(-1)

    def total(t):
        a = alloc_rows(bx, bs, bc, np.repeat(t, p_max), flat_cap).reshape(g, p_max)
        return np.where(on, a, 0.0).sum(axis=1), a

    with np.errstate(invalid="ignore"):
        t_one = ref.times_at(bx, bs, bc, np.minimum(1.0, flat_cap)).reshape(g, p_max)
    hi = np.maximum(np.where(on & (cap > 0), t_one, 0.0).max(axis=1), 1e-9)
    for _ in range(200):
        need = total(hi)[0] < n
        if not need.any():
            break
        hi = np.where(need, 2.0 * hi, hi)
    lo = np.zeros(g)
    done = n <= 0
    for _ in range(200):
        if done.all():
            break
        mid = 0.5 * (lo + hi)
        ge = total(mid)[0] >= n
        hi, lo = np.where(~done & ge, mid, hi), np.where(~done & ~ge, mid, lo)
        done = done | (hi - lo <= 1e-12 * hi)
    tot, a = total(hi)
    excess = tot - n
    scale = (tot > 0) & (excess > 0)
    with np.errstate(divide="ignore", invalid="ignore"):
        a = np.where(scale[:, None], a - excess[:, None] * (a / tot[:, None]), a)
    x = np.zeros(xs.shape[0])
    x[idx[on]] = a[on]
    return x


def partition(est: "ref.Estimates", members: List[np.ndarray], n: int, caps: np.ndarray,
              min_units: int, max_knots: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(allocations [p], group shares [g])`` for ``n`` units under ``est``."""
    xs, ss, counts = est.padded()
    caps = np.asarray(caps, dtype=np.int64)
    uncapped = bool(np.all(caps >= n))
    agg_caps = np.full(xs.shape[0], np.inf) if uncapped else caps.astype(np.float64)
    gx, gs, gc = aggregate(xs, ss, counts, members, agg_caps, max_knots)
    gcaps = np.array([int(caps[m].sum()) for m in members], dtype=np.int64)
    floors = np.array([min_units * len(m) for m in members], dtype=np.int64)
    x_g, _ = ref.continuous(gx, gs, gc, n, gcaps)
    shares = np.minimum(np.maximum(floors, np.floor(x_g).astype(np.int64)), gcaps)
    shares = complete(gx, gs, gc, x_g, shares, gcaps, floors, n)
    x = inner_continuous(xs, ss, counts, members, shares, caps)
    d = np.zeros(xs.shape[0], dtype=np.int64)
    for m, share in zip(members, shares):
        dm = np.minimum(np.maximum(min_units, np.floor(x[m]).astype(np.int64)), caps[m])
        d[m] = complete(xs[m], ss[m], counts[m], x[m], dm, caps[m],
                        np.full(len(m), min_units), int(share))
    return d, shares


def makespan(est: "ref.Estimates", d) -> float:
    """The largest predicted time of an allocation under ``est``."""
    xs, ss, counts = est.padded()
    d = np.asarray(d, dtype=np.float64)
    t = ref.times_at(xs, ss, counts, d)
    return float(np.max(np.where(d > 0, t, 0.0)))
