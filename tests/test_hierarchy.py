"""Two-level hierarchical partitioning: the exactness-tier battery.

The contract (``core/hierarchy.py`` module docstring):

* **single group == flat, bit-identical** — the outer level degenerates to
  "give the one group all n" and the inner solve IS the flat kernel, on
  numpy, jax, and jax+shard_map alike;
* **multi-group == flat makespan within the aggregation tolerance** — group
  aggregates are exact at sampled knots and interpolate between them, so
  allocations may shift a boundary unit but the makespan never degrades
  beyond the interpolation + integer-boundary error (asserted at 12% over
  the fuzz lanes — empirical worst over 340 random monotone cases is
  ~1.10 — for monotone banks at n >= 30 p so per-unit granularity
  does not dominate; non-monotone banks get structural checks only — their
  alloc-at-time functions JUMP, which no sampled aggregate can bound);
* **per-group completion routing** — an adversarial non-monotone group
  demotes only its OWN inner solve: auto always equals the exact greedy
  completion, and the jax block path matches the numpy per-group loop;
* **error parity** — validation raises the flat paths' messages in the flat
  paths' order, so the Scheduler facade keeps one error surface.

Fuzz lanes follow the repo convention: tier-1 smoke (25 cases) plus a
>= 200-case ``slow`` lane.  The sharded tests run under however many
devices the host exposes (CI's emulated-multi-device lane sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""

import numpy as np
import pytest

import jax

from repro.core import ModelBank, Policy, Scheduler, SpeedStore
from repro.core.hierarchy import Hierarchy
from repro.core.partition import _partition_units_bank
from repro.fleet import FleetScheduler, JobSpec

BIT_EXACT = jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# Random banks and the makespan oracle
# ---------------------------------------------------------------------------


def _random_bank(rng, p, kmax=5, monotone=True):
    """Per-row random piecewise models; ``monotone=True`` draws increasing
    knot times (the threshold-count precondition), else free speeds."""
    pts = []
    for _ in range(p):
        k = int(rng.integers(1, kmax + 1))
        xs = np.unique(np.round(rng.uniform(1.0, 200.0, k), 3))
        if monotone:
            ts = np.sort(rng.uniform(0.1, 10.0, len(xs)))
            ss = xs / ts
        else:
            ss = rng.uniform(0.5, 20.0, len(xs))
        pts.append((list(xs), list(ss)))
    return ModelBank.from_point_lists(pts)


def _makespan(bank, d):
    d = np.asarray(d, dtype=np.float64)
    t = bank.time(np.maximum(d, 1.0))
    return float(np.max(np.where(d > 0, t, 0.0)))


def _random_case(rng, p_hi=40):
    p = int(rng.integers(2, p_hi))
    g = int(rng.integers(1, min(p, 8) + 1))
    groups = rng.integers(0, g, size=p).tolist()
    bank = _random_bank(rng, p, monotone=bool(rng.random() < 0.7))
    n = int(rng.integers(30 * p, 120 * p))
    min_units = int(rng.integers(0, 2))
    caps = None
    if rng.random() < 0.35:
        lo = max(1, min_units)
        caps = [lo + int(f * n) for f in rng.uniform(0.6, 1.0, p)]
    return dict(bank=bank, groups=groups, n=n, min_units=min_units, caps=caps)


def _check_hier_vs_flat(case, *, backend="numpy", sharding=None, tol=0.12):
    bank, groups = case["bank"], case["groups"]
    n, mu, caps = case["n"], case["min_units"], case["caps"]
    d_flat, _ = _partition_units_bank(
        bank, n, caps if caps is not None else [n] * bank.p, min_units=mu
    )
    h = Hierarchy.from_bank(bank, groups, backend=backend, sharding=sharding)
    d_hier = h.partition_units(n, caps, min_units=mu)

    assert sum(d_hier) == n
    icaps = caps if caps is not None else [n] * bank.p
    assert all(0 <= v <= c for v, c in zip(d_hier, icaps))
    assert all(v >= mu for v in d_hier)
    if bank.is_monotone():
        m_flat = _makespan(bank, d_flat)
        m_hier = _makespan(bank, d_hier)
        assert m_hier <= m_flat * (1.0 + tol) + 1e-12, (m_hier, m_flat)
    if len(set(groups)) == 1:
        assert d_hier == [int(v) for v in d_flat]
    return d_hier


# ---------------------------------------------------------------------------
# Tier-1: smoke fuzz + bit-identity + routing + errors
# ---------------------------------------------------------------------------


def test_hier_vs_flat_smoke_fuzz_numpy():
    rng = np.random.default_rng(201)
    for _ in range(25):
        _check_hier_vs_flat(_random_case(rng))


def test_hier_jax_matches_numpy_smoke():
    """The jax lax.map block path returns exactly the numpy per-group loop
    (bit-identical on CPU under x64)."""
    rng = np.random.default_rng(202)
    with jax.enable_x64():
        for _ in range(6):
            case = _random_case(rng, p_hi=20)
            d_np = _check_hier_vs_flat(case, backend="numpy")
            d_jx = _check_hier_vs_flat(case, backend="jax")
            if BIT_EXACT:
                assert d_np == d_jx


def test_single_group_bit_identical_all_backends():
    """g=1 degenerates to the flat solve on every inner backend."""
    rng = np.random.default_rng(203)
    bank = _random_bank(rng, 23)
    n = 907
    d_flat, _ = _partition_units_bank(bank, n, [n] * bank.p, min_units=0)
    with jax.enable_x64():
        for backend, sharding in [("numpy", None), ("jax", None), ("jax", "shard_map")]:
            h = Hierarchy.from_bank(bank, [0] * bank.p, backend=backend, sharding=sharding)
            d = h.partition_units(n)
            if BIT_EXACT:
                assert d == [int(v) for v in d_flat], (backend, sharding)
            else:  # pragma: no cover - accelerator hosts
                assert sum(d) == n


def test_shard_map_matches_unsharded():
    """shard_map over the host's devices returns exactly the single-program
    jax path, for group counts that do and don't divide the device count."""
    rng = np.random.default_rng(204)
    with jax.enable_x64():
        for g in (1, 2, 5, len(jax.devices()) + 1):
            p = 6 * g
            groups = (np.arange(p) % g).tolist()
            bank = _random_bank(rng, p)
            n = int(rng.integers(p, 40 * p))
            h_jax = Hierarchy.from_bank(bank, groups, backend="jax")
            h_shd = Hierarchy.from_bank(bank, groups, backend="jax", sharding="shard_map")
            assert h_shd.partition_units(n) == h_jax.partition_units(n)


def test_shard_map_memory_gate():
    """Under shard_map no device holds more than ceil(g/ndev) group blocks —
    the p=10^6 memory story, checked structurally via max_shard_elems."""
    rng = np.random.default_rng(205)
    ndev = len(jax.devices())
    g, per = 8, 5
    banks = [_random_bank(rng, per, kmax=3) for _ in range(g)]
    h_shd = Hierarchy.from_group_banks(banks, backend="jax", sharding="shard_map")
    h_all = Hierarchy.from_group_banks(banks, backend="jax")
    k = max(int(b.xs.shape[1]) for b in banks)
    assert h_shd.max_shard_elems() == 2 * (-(-g // ndev)) * per * k
    assert h_all.max_shard_elems() == 2 * g * per * k
    if ndev > 1:
        assert h_shd.max_shard_elems() < h_all.max_shard_elems()


def test_nonmonotone_group_demotes_only_itself():
    """One group's time function DROPS past a knee (observed speed jumps:
    non-monotone).  auto must equal the exact greedy completion, and the jax
    per-group routing must match numpy — the monotone neighbours keep their
    threshold fast path without being poisoned."""
    rng = np.random.default_rng(206)
    good = _random_bank(rng, 12, monotone=True)
    # non-monotone rows: speed jumps 10x at x=50 (time drops)
    bad_pts = [([10.0, 50.0, 60.0], [s, s, 10.0 * s]) for s in rng.uniform(2.0, 8.0, 6)]
    bad = ModelBank.from_point_lists(bad_pts)
    bank = ModelBank.from_point_lists(
        [(list(b.xs[i][: b.counts[i]]), list(b.ss[i][: b.counts[i]]))
         for b in (good, bad) for i in range(b.p)]
    )
    assert bank.is_monotone() is False
    groups = [0] * good.p + [1] * bad.p
    sub_monos = [
        Hierarchy.from_bank(bank, groups).sub_banks[i].is_monotone() for i in (0, 1)
    ]
    assert sub_monos == [True, False]
    n = 1500
    with jax.enable_x64():
        d_auto_np = Hierarchy.from_bank(bank, groups).partition_units(n)
        d_greedy_np = Hierarchy.from_bank(bank, groups).partition_units(
            n, completion="greedy"
        )
        d_auto_jx = Hierarchy.from_bank(bank, groups, backend="jax").partition_units(n)
    assert d_auto_np == d_greedy_np
    if BIT_EXACT:
        assert d_auto_jx == d_auto_np
    assert sum(d_auto_np) == n


def test_error_parity_with_flat():
    rng = np.random.default_rng(207)
    bank = _random_bank(rng, 8)
    h = Hierarchy.from_bank(bank, [0, 0, 1, 1, 2, 2, 3, 3])
    with pytest.raises(ValueError, match="unknown completion mode"):
        h.partition_units(10, completion="bogus")
    with pytest.raises(ValueError, match="n must be non-negative"):
        h.partition_units(-1)
    with pytest.raises(ValueError, match=r"infeasible: sum\(caps\)"):
        h.partition_units(100, [2] * 8)
    with pytest.raises(ValueError, match="min_units=3 infeasible"):
        h.partition_units(10, min_units=3)
    assert h.partition_units(0) == [0] * 8
    # empty FPM row with a positive cap, same message as the flat bank path
    pts = [([1.0], [1.0])] * 4
    empty = ModelBank.from_point_lists(pts)
    empty.counts = np.array([1, 1, 0, 1])
    h2 = Hierarchy.from_bank(empty, [0, 0, 1, 1])
    with pytest.raises(ValueError, match="empty FPM"):
        h2.partition_units(4)
    with pytest.raises(ValueError, match="groups must be a length-p"):
        Hierarchy.from_bank(bank, [0, 1])
    with pytest.raises(ValueError, match="unknown hierarchy backend"):
        Hierarchy.from_bank(bank, [0] * 8, backend="scalar")
    with pytest.raises(ValueError, match='requires backend="jax"'):
        Hierarchy.from_bank(bank, [0] * 8, backend="numpy", sharding="shard_map")


# ---------------------------------------------------------------------------
# Scheduler facade routing
# ---------------------------------------------------------------------------


def test_scheduler_hier_routing():
    rng = np.random.default_rng(208)
    bank = _random_bank(rng, 16)
    n = 800
    flat = Scheduler(SpeedStore.from_bank(bank)).partition(n)
    hier1 = Scheduler(
        SpeedStore.from_bank(bank), policy=Policy.HIER, groups=[0] * 16
    ).partition(n)
    assert hier1.allocations == flat.allocations
    hier4 = Scheduler(
        SpeedStore.from_bank(bank), groups=[i % 4 for i in range(16)]
    ).partition(n)
    assert sum(hier4.allocations) == n
    assert _makespan(bank, hier4.allocations) <= _makespan(bank, flat.allocations) * 1.05

    with pytest.raises(ValueError, match="policy=HIER requires a groups="):
        Scheduler(SpeedStore.from_bank(bank), policy=Policy.HIER)

    s = Scheduler(SpeedStore.from_bank(bank), groups=[i % 4 for i in range(16)])
    st = s.state_dict()
    assert st["groups"] == [i % 4 for i in range(16)]
    s2 = Scheduler.from_state(st)
    assert s2.partition(n).allocations == hier4.allocations
    # mid-flight regrouping
    s2.set_groups([0] * 16)
    assert s2.partition(n).allocations == flat.allocations
    s2.set_groups(None)
    assert s2.partition(n).allocations == flat.allocations


# ---------------------------------------------------------------------------
# FleetScheduler routing
# ---------------------------------------------------------------------------


class _FleetExec:
    """q-job wrapper over a shared per-processor batch time function."""

    def __init__(self, p, seed=3):
        r = np.random.default_rng(seed)
        self.base = r.uniform(5.0, 50.0, size=p)
        self.bend = r.uniform(50, 400, size=p)
        self.num_procs = p

    def _times(self, d):
        d = np.asarray(d, dtype=np.float64)
        s = self.base * (1.0 + 0.3 * np.minimum(d, self.bend) / self.bend)
        return np.where(d > 0, d / s, 0.0)

    def run_jobs(self, names, D):
        return np.stack([self._times(d) for d in D])


def _run_fleet(p, **kw):
    fs = FleetScheduler(p, **kw)
    fs.admit(JobSpec(name="a", n=2000, eps=0.02, max_iter=10))
    fs.admit(JobSpec(name="b", n=3333, eps=0.02, max_iter=10))
    res = fs.run(_FleetExec(p), max_rounds=16)
    return {k: (v.allocations, v.makespan) for k, v in res.items()}


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_fleet_hier_single_group_matches_flat(backend):
    p = 24
    with jax.enable_x64():
        flat = _run_fleet(p, backend=backend)
        hier = _run_fleet(p, backend=backend, groups=[0] * p)
    if BIT_EXACT:
        assert flat == hier
    else:  # pragma: no cover - accelerator hosts
        assert flat.keys() == hier.keys()


def test_fleet_hier_multigroup_converges():
    p = 24
    groups = [i % 3 for i in range(p)]
    with jax.enable_x64():
        flat = _run_fleet(p, backend="jax")
        hier = _run_fleet(p, backend="jax", groups=groups)
    for k in flat:
        assert sum(hier[k][0]) == sum(flat[k][0])
        assert hier[k][1] <= flat[k][1] * 1.05 + 1e-9


def test_fleet_hier_validation():
    with pytest.raises(ValueError, match="hierarchical fleet requires"):
        FleetScheduler(4, backend="scalar", groups=[0] * 4)
    with pytest.raises(ValueError, match="length-p"):
        FleetScheduler(4, backend="numpy", groups=[0] * 3)
    with pytest.raises(ValueError, match='requires backend="jax"'):
        FleetScheduler(4, backend="numpy", sharding="shard_map")
    with pytest.raises(ValueError, match="unknown sharding"):
        FleetScheduler(4, backend="jax", sharding="bogus")


# ---------------------------------------------------------------------------
# Slow fuzz lanes
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_hier_vs_flat_fuzz_numpy_lane():
    rng = np.random.default_rng(210)
    for _ in range(200):
        _check_hier_vs_flat(_random_case(rng))


@pytest.mark.slow
def test_hier_fuzz_jax_lane():
    """100 fuzzed cases through the jax block path (shapes vary, so the jit
    cache churns more than the flat stacked tests — kept to 100)."""
    rng = np.random.default_rng(211)
    with jax.enable_x64():
        for _ in range(100):
            case = _random_case(rng, p_hi=24)
            d_np = _check_hier_vs_flat(case, backend="numpy")
            d_jx = _check_hier_vs_flat(case, backend="jax")
            if BIT_EXACT:
                assert d_np == d_jx


@pytest.mark.slow
def test_hier_shard_map_fuzz_lane():
    rng = np.random.default_rng(212)
    with jax.enable_x64():
        for _ in range(40):
            case = _random_case(rng, p_hi=24)
            d_jx = _check_hier_vs_flat(case, backend="jax")
            d_sh = _check_hier_vs_flat(case, backend="jax", sharding="shard_map")
            assert d_jx == d_sh


@pytest.mark.slow
def test_hier_p1e4_smoke():
    """p=10^4 in groups of 100: the cache-wall shape, solved hierarchically
    and checked against the flat makespan."""
    rng = np.random.default_rng(213)
    p, gsize = 10_000, 100
    bank = _random_bank(rng, p, kmax=4)
    groups = (np.arange(p) // gsize).tolist()
    case = dict(bank=bank, groups=groups, n=20 * p, min_units=0, caps=None)
    _check_hier_vs_flat(case, tol=0.05)


def test_make_fleet_bank_matches_make_fleet():
    """The vectorized benchmark bank builder (the only way to stand up the
    p=10^6 row's group banks) must produce the same fleet as the per-model
    reference generator for identical seeds."""
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmarks.partition_scale import make_fleet, make_fleet_bank

    p = 200
    ref = ModelBank.from_models(make_fleet(p, seed=7))
    fast = make_fleet_bank(p, seed=7)
    assert fast.p == ref.p
    np.testing.assert_array_equal(fast.counts, ref.counts)
    np.testing.assert_allclose(fast.xs, ref.xs, rtol=1e-9)
    np.testing.assert_allclose(fast.ss, ref.ss, rtol=1e-9)
    assert fast.is_monotone()
    # the solve agrees too: same fleet -> same allocation
    n = 100 * p
    d_ref, _ = _partition_units_bank(ref, n, [n] * p, min_units=1)
    d_fast, _ = _partition_units_bank(fast, n, [n] * p, min_units=1)
    assert d_ref == d_fast
