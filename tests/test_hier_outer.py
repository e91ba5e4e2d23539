"""The two-level route's outer level, vectorized over groups, against the
per-group loops it replaced (kept here as the references), and against the
benchmark's plain two-level reference; and ``Scheduler.autotune``'s route.

The loop versions below are the code as it stood before the outer level was
vectorized: the vectorized code keeps their arithmetic, so the comparison
is bit for bit.
"""

import sys
from pathlib import Path
from typing import List, Tuple

import numpy as np
import pytest

import jax

from repro.core import ModelBank, Scheduler, SpeedStore
from repro.core.hierarchy import Hierarchy, complete_groups
from repro.core.modelbank import (
    aggregate_allocs_host,
    aggregate_bank,
    aggregate_times,
    group_index,
)

BIT_EXACT = jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# The per-group loops as they were
# ---------------------------------------------------------------------------


def _alloc_at_times(bank: ModelBank, ts: np.ndarray, caps: np.ndarray) -> np.ndarray:
    """The per-group member allocations at sample times, as before vectorization."""
    ts = np.asarray(ts, dtype=np.float64)[:, None]  # [T, 1]
    caps2 = np.broadcast_to(np.asarray(caps, dtype=np.float64), (bank.p,))[None, :]
    first_x, first_s, last_x, last_s = bank._edges()

    best = np.minimum(ts * first_s[None, :], np.minimum(first_x[None, :], caps2))

    k_max = bank.xs.shape[1]
    if k_max >= 2:
        x0, x1 = bank.xs[None, :, :-1], bank.xs[None, :, 1:]
        s0, s1 = bank.ss[None, :, :-1], bank.ss[None, :, 1:]
        seg = np.arange(k_max - 1)[None, None, :]
        valid = (
            (seg < (bank.counts - 1)[None, :, None])
            & (x0 < caps2[..., None])
            & (x1 > x0)
        )
        x1c = np.minimum(x1, caps2[..., None])
        denom = np.where(x1 > x0, x1 - x0, 1.0)
        m = (s1 - s0) / denom
        tseg = ts[..., None]  # [T, 1, 1]
        a = 1.0 - tseg * m
        b = tseg * (s0 - m * x0)
        with np.errstate(divide="ignore", invalid="ignore"):
            ub = b / np.where(a != 0.0, a, 1.0)
        cand = np.where(
            a > 0.0,
            np.where(ub >= x0, np.minimum(ub, x1c), 0.0),
            np.where(
                a == 0.0,
                np.where(b >= 0.0, x1c, 0.0),
                np.where(x1c >= ub, x1c, 0.0),
            ),
        )
        cand = np.where(valid, cand, 0.0)
        best = np.maximum(best, cand.max(axis=-1))

    ub_r = ts * last_s[None, :]
    right = (caps2 > last_x[None, :]) & (ub_r >= last_x[None, :]) & (bank.counts > 0)[None, :]
    best = np.maximum(best, np.where(right, np.minimum(ub_r, caps2), 0.0))

    best = np.where((caps2 > 0.0) & (bank.counts > 0)[None, :], best, 0.0)
    return np.where(ts > 0.0, best, 0.0)


def _aggregate_one(
    sub: ModelBank, caps: np.ndarray, max_knots: int
) -> Tuple[List[float], List[float]]:
    """One group's aggregate knots, as before vectorization."""
    ts = _aggregate_times(sub, caps, max_knots)
    if ts.size == 0:
        return [], []
    caps_f = caps.astype(np.float64)
    k = sub.xs.shape[1]
    # _alloc_at_times materializes ~a dozen [T_chunk, p, k-1] temporaries;
    # chunk the time axis so each slab stays ~1 MB and the whole working set
    # L2-resident — the pass is memory-bandwidth bound, and cache blocking
    # here measures ~1.8x at fleet group shapes (p_g=1000, k~17) while also
    # keeping p ~ 10^5 member groups allocatable at p=10^6.  Chunk
    # boundaries cannot change any element's arithmetic, so the result is
    # bitwise independent of the chunk size.
    t_chunk = max(1, int(131_072 // max(sub.p * max(k, 1), 1)))
    xs_g = np.concatenate(
        [
            _alloc_at_times(sub, ts[i : i + t_chunk], caps_f).sum(axis=1)
            for i in range(0, ts.size, t_chunk)
        ]
    )
    return _points_from_samples(ts, xs_g)


def _aggregate_times(sub: ModelBank, caps: np.ndarray, max_knots: int) -> np.ndarray:
    """One group's sample times, as before vectorization."""
    k = sub.xs.shape[1]
    valid = (np.arange(k)[None, :] < sub.counts[:, None]) & (caps[:, None] > 0)
    with np.errstate(divide="ignore", invalid="ignore"):
        t_pts = np.where(sub.ss > 0, sub.xs / sub.ss, np.nan)
    ts = t_pts[valid]
    active = (caps > 0) & (sub.counts > 0)
    if np.any(active):
        cap_t = sub.time(np.where(active, caps, 1.0))
        ts = np.concatenate([ts, cap_t[active]])
    ts = np.unique(ts[np.isfinite(ts) & (ts > 0)])
    if ts.size == 0:
        return ts
    quota = max(max_knots // 4, 2)
    if ts.size > quota:
        pick = np.unique(np.round(np.linspace(0, ts.size - 1, quota)).astype(int))
        ts = ts[pick]
    # Jump brackets FIRST, on the knot/cap-derived times only: member alloc
    # functions can step exactly AT a knot time, never between knots, so the
    # geometric fill below (curvature sampling in wide gaps) needs no
    # brackets — skipping them keeps the sampled grid (and the group bank's
    # knot count) ~25% smaller for the same accuracy.
    ts = np.unique(np.concatenate([ts, ts * (1.0 - 1e-9)]))
    if ts[-1] > ts[0]:
        ts = np.unique(np.concatenate([ts, np.geomspace(ts[0], ts[-1], quota)]))
    return ts


def _points_from_samples(
    ts: np.ndarray, xs_g: np.ndarray
) -> Tuple[List[float], List[float]]:
    """Sampled (time, size) pairs to knot lists, as before vectorization."""
    keep = xs_g > 0
    # equal-X plateaus (all members capped): keep the FIRST (earliest-time,
    # fastest) occurrence — the true aggregate reaches that size then.
    keep &= np.concatenate([[True], np.diff(xs_g) > 0])
    ts, xs_g = ts[keep], xs_g[keep]
    return list(xs_g), list(xs_g / ts)


def _complete_loop(gbank, shares, gcaps, rem, leftover):
    """The outer completion as it was: one scan over every group per unit."""
    shares = np.array(shares, dtype=np.int64)
    g = len(shares)
    for _ in range(leftover):
        best_i, best_key = -1, None
        for i in range(g):
            if shares[i] + 1 > gcaps[i]:
                continue
            key = (gbank.time_one(i, float(shares[i] + 1)), -float(rem[i]))
            if best_key is None or key < best_key:
                best_i, best_key = i, key
        if best_i < 0:
            raise ValueError("caps infeasible during integer completion")
        shares[best_i] += 1
    return shares


# ---------------------------------------------------------------------------
# Seeded random banks
# ---------------------------------------------------------------------------


def _random_bank(rng, p, kmax=6, monotone=True, empty=0.0):
    pts = []
    for _ in range(p):
        k = 0 if rng.random() < empty else int(rng.integers(1, kmax + 1))
        xs = np.unique(np.round(rng.uniform(1.0, 2000.0, k)))
        if monotone:
            ss = xs / np.sort(rng.uniform(0.1, 10.0, len(xs)))
        else:
            ss = rng.uniform(0.5, 20.0, len(xs))
        pts.append((list(xs), list(ss)))
    return ModelBank.from_point_lists(pts)


def _node_groups(rng, n_groups, shuffle=False):
    """Groups of 5 and 2 members, as a platform's GPU and CPU nodes."""
    groups = np.repeat(np.arange(n_groups), rng.choice([5, 2], size=n_groups))
    return rng.permutation(groups) if shuffle else groups


def _reference():
    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from bench import hier_reference, reference

    return reference, hier_reference


# ---------------------------------------------------------------------------
# Aggregation: all groups at once == one group at a time
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(6))
def test_aggregation_matches_the_per_group_loop(seed):
    rng = np.random.default_rng(300 + seed)
    for case in range(12):
        p = int(rng.integers(1, 80 if case % 2 else 240))
        g = int(rng.integers(1, min(p, 14 if case % 2 else 4) + 1))
        groups = rng.integers(0, g, size=p)
        bank = _random_bank(rng, p, kmax=int(rng.integers(1, 14)),
                            monotone=rng.random() < 0.7, empty=0.05)
        if rng.random() < 0.5:
            caps = np.full(p, np.inf)
        else:
            caps = np.round(rng.uniform(0, 300, p)) * (rng.random(p) < 0.9)
        max_knots = int(rng.choice([8, 20, 64]))
        index = group_index(groups)
        ts, count = aggregate_times(bank, index, caps, max_knots)
        gbank = aggregate_bank(ts, count, aggregate_allocs_host(bank, index, caps, ts))
        for gi, m in enumerate(index.members):
            sub = ModelBank(xs=bank.xs[m], ss=bank.ss[m], counts=bank.counts[m])
            t_old = _aggregate_times(sub, caps[m], max_knots)
            np.testing.assert_array_equal(ts[gi, : count[gi]], t_old)
            px, ps = _aggregate_one(sub, caps[m], max_knots)
            c = int(gbank.counts[gi])
            assert c == len(px)
            np.testing.assert_array_equal(gbank.xs[gi, :c], np.asarray(px))
            np.testing.assert_array_equal(gbank.ss[gi, :c], np.asarray(ps))


def test_group_index_gathers_and_scatters_members():
    rng = np.random.default_rng(310)
    groups = rng.integers(3, 40, size=200)
    index = group_index(groups)
    assert index.gids.tolist() == sorted(set(groups.tolist()))
    for gid, m in zip(index.gids, index.members):
        assert m.tolist() == np.flatnonzero(groups == gid).tolist()
    v = rng.permutation(200)
    np.testing.assert_array_equal(index.scatter(index.gather(v), 200), v)


# ---------------------------------------------------------------------------
# Outer completion: bulk picks == the unit-by-unit scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("monotone", [True, False])
def test_outer_completion_matches_the_greedy_loop(monotone):
    rng = np.random.default_rng(320 + monotone)
    for _ in range(40):
        g = int(rng.integers(1, 60))
        gbank = _random_bank(rng, g, kmax=8, monotone=monotone)
        gbank.monotone = None
        shares = rng.integers(0, 400, size=g)
        gcaps = shares + rng.integers(0, 6, size=g) * (rng.random(g) < 0.8)
        gcaps = np.where(rng.random(g) < 0.1, shares + 10**6, gcaps)
        rem = np.round(rng.random(g), 2)  # ties in the remainder too
        room = int((gcaps - shares).sum())
        leftover = int(rng.integers(0, min(room, 3 * g) + 1))
        expect = _complete_loop(gbank, shares, gcaps, rem, leftover)
        np.testing.assert_array_equal(
            complete_groups(gbank, shares, gcaps, rem, leftover), expect
        )
    gbank = _random_bank(rng, 3)
    with pytest.raises(ValueError, match="caps infeasible"):
        complete_groups(gbank, np.zeros(3, np.int64), np.ones(3, np.int64), np.zeros(3), 4)


# ---------------------------------------------------------------------------
# The program's two-level allocation == the benchmark's plain reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_two_level_allocation_matches_the_reference(backend):
    reference, hier_reference = _reference()
    rng = np.random.default_rng(330)
    with jax.enable_x64():
        for case in range(8):
            groups = _node_groups(rng, int(rng.integers(8, 16)), shuffle=case % 3 == 0)
            p = groups.size
            est = reference.Estimates(p)
            bank = _random_bank(rng, p)
            for row, i in zip(est.pts, range(p)):
                c = int(bank.counts[i])
                row.update(zip(bank.xs[i, :c].tolist(), bank.ss[i, :c].tolist()))
            n = int(rng.integers(3 * p, 400 * p))
            mu = int(rng.integers(0, 2))
            d_ref, _ = hier_reference.partition(
                est, hier_reference.members_of(groups), n, np.full(p, n), mu, 64
            )
            d = Hierarchy.from_bank(bank, groups, backend=backend).partition_units(
                n, min_units=mu
            )
            assert sum(d) == n
            if BIT_EXACT:
                assert d == d_ref.tolist()
            else:  # pragma: no cover - accelerator hosts
                assert np.max(np.abs(np.asarray(d) - d_ref)) <= 1


@pytest.mark.parametrize("sharding", [None, "shard_map"])
def test_inner_routes_give_the_same_allocation(monkeypatch, sharding):
    """Groups batched in one bisection and groups looped under ``lax.map``
    (the route past ``_HIER_SERIAL_MIN_GROUP_BYTES`` a group) agree."""
    from repro.core import hierarchy

    rng = np.random.default_rng(331)
    with jax.enable_x64():
        for case in range(4):
            groups = _node_groups(rng, int(rng.integers(8, 16)), shuffle=case % 2 == 1)
            p = groups.size
            bank = _random_bank(rng, p, monotone=case != 3)
            n = int(rng.integers(3 * p, 400 * p))
            got = {}
            for route, limit in (("batched", 1 << 62), ("serial", 0)):
                monkeypatch.setattr(hierarchy, "_HIER_SERIAL_MIN_GROUP_BYTES", limit)
                h = Hierarchy.from_bank(bank, groups, backend="jax", sharding=sharding)
                assert h._inner_serial(h._ensure_blocks()[0], 8) == (route == "serial")
                got[route] = h.partition_units(n, min_units=1)
            assert sum(got["serial"]) == n
            assert got["serial"] == got["batched"]


# ---------------------------------------------------------------------------
# Scheduler.autotune: groups take the two-level route, no groups the flat one
# ---------------------------------------------------------------------------


class _Cluster:
    """Per-processor times from piecewise-linear speeds with a knee."""

    def __init__(self, p, seed):
        r = np.random.default_rng(seed)
        self.num_procs = p
        self.base = r.uniform(5.0, 60.0, p)
        self.knee = r.uniform(100.0, 900.0, p)

    def run(self, d):
        d = np.asarray(d, dtype=np.float64)
        s = self.base * np.where(d < self.knee, 1.0, self.knee / np.maximum(d, 1.0)) ** 0.5
        return (d / s).tolist()


def _calls(monkeypatch):
    seen = {"hier": 0, "flat": 0}
    hier, flat = Hierarchy.partition_units, SpeedStore.partition

    def hier_spy(self, *a, **kw):
        seen["hier"] += 1
        return hier(self, *a, **kw)

    def flat_spy(self, *a, **kw):
        seen["flat"] += 1
        return flat(self, *a, **kw)

    monkeypatch.setattr(Hierarchy, "partition_units", hier_spy)
    monkeypatch.setattr(SpeedStore, "partition", flat_spy)
    return seen


def test_autotune_with_groups_takes_the_two_level_route(monkeypatch):
    reference, hier_reference = _reference()
    rng = np.random.default_rng(340)
    groups = _node_groups(rng, 12)
    p, n = groups.size, 1000 * groups.size
    seen = _calls(monkeypatch)
    with jax.enable_x64():
        sched = Scheduler(groups=groups.tolist(), backend="jax", eps=0.02, min_units=1)
        res = sched.autotune(_Cluster(p, 7), n, max_iter=12)
    assert res.diagnostics["route"] == "hier" and res.converged
    assert seen["flat"] == 0 and seen["hier"] == res.iterations - 1
    # every measured allocation after the first is the reference's two-level
    # partition of the estimates folded so far, unless that partition was
    # measured already (the loop then probes a neighbour)
    history = res.diagnostics["history"]
    est = reference.Estimates(p)
    members = hier_reference.members_of(groups)
    measured = []
    for (d_prev, t_prev), (d_next, _) in zip(history, history[1:]):
        measured.append(tuple(d_prev))
        est.fold([float(v) for v in d_prev], [a / b for a, b in zip(d_prev, t_prev)],
                 [True] * p)
        d_ref, _ = hier_reference.partition(est, members, n, np.full(p, n), 1, 64)
        if tuple(d_ref.tolist()) in measured or not BIT_EXACT:
            continue
        assert list(d_next) == d_ref.tolist()


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_autotune_without_groups_keeps_the_flat_route(monkeypatch, backend):
    """No ``groups``: every repartition is the store's flat partition, and the
    session is the one the flat partitioner gives step by step."""
    p, n = 30, 30_000
    seen = _calls(monkeypatch)
    with jax.enable_x64():
        sched = Scheduler(backend=backend, eps=0.02, min_units=1)
        res = sched.autotune(_Cluster(p, 8), n, max_iter=12)
        assert res.diagnostics["route"] == "flat" and res.converged
        assert seen["hier"] == 0 and seen["flat"] == res.iterations - 1
        history = res.diagnostics["history"]
        store = SpeedStore.empty(p, backend=backend)
        for (d_prev, t_prev), (d_next, _) in zip(history, history[1:]):
            store.fold_in([float(v) for v in d_prev],
                          [a / b for a, b in zip(d_prev, t_prev)], [True] * p)
            assert store.partition_units(n, min_units=1) == list(d_next)
