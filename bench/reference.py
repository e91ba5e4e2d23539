"""The plain reference that decides ``correct``.

It imports nothing of the program and is handed nothing the program made
except what the program served: the allocations it handed to the executor.
From those and the times the benchmark's own executor measured, it rebuilds
the estimates itself and recomputes every partition it is asked to check.

Semantics, from the paper (arXiv:1109.3074, section 2) and the scheduler's
documented behaviour:

* an estimate is a set of observed ``(x, s)`` points per processor, linear
  between points, constant outside them; a new point at an ``x`` already
  observed replaces its speed;
* a served round smooths each measured time with an exponential moving
  average keyed by (processor, allocation), ``ema = (1 - a) ema + a t``, and
  folds the point ``(d_i, d_i / ema)``; a measuring (autotune) round folds
  ``(d_i, d_i / t_i)``;
* the continuous solve finds the smallest ``t`` whose allocations
  ``max{x <= cap : x / s(x) <= t}`` sum to ``n``: a doubling bracket from
  the largest one-unit time, then bisection until ``hi - lo <= 1e-12 hi``;
  the allocations at ``t* = hi`` are scaled down by their excess over ``n``;
* the integer completion floors them, lifts them to ``min_units``, clips
  them to the caps, and gives each leftover unit to the processor with the
  smallest ``(time(d + 1), -fractional remainder, index)``.

Everything is float64 numpy or plain Python.
"""

from __future__ import annotations

import bisect
import heapq
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class Estimates:
    """One partial speed function per processor, as a dict ``x -> s``."""

    def __init__(self, p: int):
        self.pts: List[Dict[float, float]] = [dict() for _ in range(p)]

    @property
    def p(self) -> int:
        return len(self.pts)

    def fold(self, x: Sequence[float], s: Sequence[float], valid: Sequence[bool]):
        for row, xi, si, ok in zip(self.pts, x, s, valid):
            if ok:
                row[xi] = si

    def padded(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Sorted knots as ``[p, K]`` arrays padded with each row's last
        knot, and the knot counts."""
        counts = np.asarray([len(r) for r in self.pts], dtype=np.int64)
        k = max(int(counts.max(initial=1)), 1)
        xs = np.zeros((self.p, k))
        ss = np.zeros((self.p, k))
        for i, row in enumerate(self.pts):
            if not row:
                continue
            xr = sorted(row)
            sr = [row[x] for x in xr]
            xs[i, : len(xr)] = xr
            ss[i, : len(xr)] = sr
            xs[i, len(xr):] = xr[-1]
            ss[i, len(xr):] = sr[-1]
        return xs, ss, counts


class FlatSession:
    """The reference's view of one flat job: its estimates and the EMA."""

    def __init__(self, p: int, smooth: float):
        self.est = Estimates(p)
        self.smooth = float(smooth)
        self.ema: Dict[Tuple[int, int], float] = {}

    def fold_measured(self, d: Sequence[int], t: Sequence[float]) -> None:
        x = [float(v) for v in d]
        s = [di / ti if (di > 0 and ti > 0) else 1.0 for di, ti in zip(d, t)]
        self.est.fold(x, s, [di > 0 and ti > 0 for di, ti in zip(d, t)])

    def fold_served(self, d: Sequence[int], t: Sequence[float]) -> None:
        a = self.smooth
        x, s, ok = [], [], []
        for i, (di, ti) in enumerate(zip(d, t)):
            x.append(float(di))
            if di <= 0 or ti <= 0:
                s.append(1.0)
                ok.append(False)
                continue
            prev = self.ema.get((i, di))
            e = ti if prev is None else (1 - a) * prev + a * ti
            self.ema[(i, di)] = e
            s.append(di / e)
            ok.append(True)
        self.est.fold(x, s, ok)


# -- the partitioner ----------------------------------------------------------


def alloc_at_time(xs, ss, counts, t: float, caps: np.ndarray) -> np.ndarray:
    """``max{x in [0, cap] : x / s(x) <= t}`` for every row, in closed form
    per linear segment (``x (1 - t m) <= t (s0 - m x0)``)."""
    if t <= 0.0:
        return np.zeros(xs.shape[0])
    first_x, first_s = xs[:, 0], ss[:, 0]
    last = np.maximum(counts - 1, 0)
    rows = np.arange(xs.shape[0])
    last_x, last_s = xs[rows, last], ss[rows, last]
    best = np.minimum(t * first_s, np.minimum(first_x, caps))
    k = xs.shape[1]
    if k >= 2:
        x0, x1 = xs[:, :-1], xs[:, 1:]
        s0, s1 = ss[:, :-1], ss[:, 1:]
        seg = np.arange(k - 1)[None, :] < (counts - 1)[:, None]
        seg &= (x0 < caps[:, None]) & (x1 > x0)
        x1c = np.minimum(x1, caps[:, None])
        with np.errstate(divide="ignore", invalid="ignore"):
            m = (s1 - s0) / np.where(x1 > x0, x1 - x0, 1.0)
            a = 1.0 - t * m
            b = t * (s0 - m * x0)
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
    return np.where((caps > 0.0) & (counts > 0), best, 0.0)


def speed_at(xs_row: Sequence[float], ss_row: Sequence[float], x: float) -> float:
    if x <= xs_row[0]:
        return ss_row[0]
    if x >= xs_row[-1]:
        return ss_row[-1]
    k = bisect.bisect_right(xs_row, x) - 1
    x0, x1 = xs_row[k], xs_row[k + 1]
    s0, s1 = ss_row[k], ss_row[k + 1]
    w = (x - x0) / (x1 - x0)
    return s0 + w * (s1 - s0)


def times_at(xs, ss, counts, x) -> np.ndarray:
    """``x_i / s_i(x_i)`` for every row (0 where ``x_i <= 0``): the speed is
    linear between knots and constant outside them."""
    x = np.asarray(x, dtype=np.float64)
    rows = np.arange(xs.shape[0])
    last = np.maximum(counts - 1, 0)
    k = np.clip(np.sum(xs <= x[:, None], axis=1) - 1, 0, np.maximum(counts - 2, 0))
    k1 = np.minimum(k + 1, xs.shape[1] - 1)
    x0, x1 = xs[rows, k], xs[rows, k1]
    s0, s1 = ss[rows, k], ss[rows, k1]
    w = (x - x0) / np.where(x1 > x0, x1 - x0, 1.0)
    s = np.where(x <= xs[:, 0], ss[:, 0],
                 np.where(x >= xs[rows, last], ss[rows, last], s0 + w * (s1 - s0)))
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(x > 0.0, x / s, 0.0)


def continuous(xs, ss, counts, n: int, caps: np.ndarray):
    """Continuous optimal allocations and ``t*``."""
    caps = np.minimum(caps.astype(np.float64), float(n))
    active = caps > 0.0
    t_one = times_at(xs, ss, counts, np.minimum(1.0, caps))
    hi = max(float(np.max(np.where(active, t_one, 0.0))), 1e-9)
    for _ in range(200):
        if alloc_at_time(xs, ss, counts, hi, caps).sum() >= n:
            break
        hi *= 2.0
    lo = 0.0
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if alloc_at_time(xs, ss, counts, mid, caps).sum() >= n:
            hi = mid
        else:
            lo = mid
        if hi - lo <= 1e-12 * hi:
            break
    x = alloc_at_time(xs, ss, counts, hi, caps)
    total = float(x.sum())
    if total > 0 and total - n > 0:
        x = x - (total - n) * (x / total)
    return x, hi


def partition(est: Estimates, n: int, caps: np.ndarray, min_units: int):
    """Integer allocations and ``t*`` for ``n`` units under ``est``."""
    xs, ss, counts = est.padded()
    caps = np.asarray(caps, dtype=np.int64)
    x, t_star = continuous(xs, ss, counts, n, caps)
    d = np.minimum(np.maximum(min_units, np.floor(x).astype(np.int64)), caps)
    leftover = int(n - d.sum())
    if leftover < 0:
        per_unit = times_at(xs, ss, counts, d.astype(np.float64)) / np.maximum(d, 1)
        order = sorted(range(len(d)), key=lambda i: per_unit[i], reverse=True)
        k = 0
        while leftover < 0:
            i = order[k % len(d)]
            if d[i] > min_units:
                d[i] -= 1
                leftover += 1
            k += 1
    rem = x - np.floor(x)
    if leftover > 0:
        t_next = times_at(xs, ss, counts, (d + 1).astype(np.float64))
        heap = [(t_next[i], -rem[i], i) for i in range(len(d)) if d[i] + 1 <= caps[i]]
        heapq.heapify(heap)
        rows = {}
        while leftover > 0:
            if not heap:
                raise ValueError("caps infeasible")
            _, negrem, i = heapq.heappop(heap)
            d[i] += 1
            leftover -= 1
            if d[i] + 1 <= caps[i]:
                if i not in rows:
                    c = int(counts[i])
                    rows[i] = (xs[i, :c].tolist(), ss[i, :c].tolist())
                xr, sr = rows[i]
                x1 = float(d[i] + 1)
                heapq.heappush(heap, (x1 / speed_at(xr, sr, x1), negrem, i))
    return d, t_star


def imbalance(times: Sequence[float]) -> float:
    ts = [float(t) for t in times if float(t) > 0.0]
    if len(ts) < 2:
        return 0.0
    return (max(ts) - min(ts)) / min(ts)


def probe_neighbour(d, times, seen, caps, min_units) -> Optional[List[int]]:
    """The first unseen one-unit move from the slowest processors to the
    fastest (the measuring loop's escape from a fixed point)."""
    p = len(d)
    slow = sorted(range(p), key=lambda i: times[i], reverse=True)
    fast = sorted(range(p), key=lambda i: times[i])
    for i in slow:
        if d[i] - 1 < min_units:
            continue
        for j in fast:
            if i == j or (caps is not None and d[j] + 1 > caps[j]):
                continue
            cand = list(d)
            cand[i] -= 1
            cand[j] += 1
            if tuple(cand) not in seen:
                return cand
    return None


# -- comparisons --------------------------------------------------------------


def estimate_gap(est: Estimates, xs_prog, ss_prog, counts_prog) -> float:
    """Largest relative gap between the program's knots and the
    reference's (1.0 where a row's knot count differs)."""
    xs, ss, counts = est.padded()
    counts_prog = np.asarray(counts_prog, dtype=np.int64)
    if not np.array_equal(counts, counts_prog):
        return 1.0
    k = xs.shape[1]
    mask = np.arange(k)[None, :] < counts[:, None]
    xp = np.asarray(xs_prog, dtype=np.float64)[:, :k]
    sp = np.asarray(ss_prog, dtype=np.float64)[:, :k]
    with np.errstate(divide="ignore", invalid="ignore"):
        gx = np.where(mask, np.abs(xp - xs) / np.abs(xs), 0.0)
        gs = np.where(mask, np.abs(sp - ss) / np.abs(ss), 0.0)
    g = float(max(gx.max(initial=0.0), gs.max(initial=0.0)))
    return g if math.isfinite(g) else 1.0
