"""Energy subsystem: bi-objective banks, Pareto fronts, capped partitions.

The energy bank IS a speed bank over the energy-rate representation
``er_i(x) = x / E_i(x)`` (see ``core/energy.py``), so the whole fuzz-parity
regime of ``test_modelbank_jax.py`` applies verbatim one level up.  This
suite locks:

  * energy queries (``energy_at`` / ``fleet_energy``) bit-identical between
    the numpy and jax banks (x64), elementwise equal to the scalar
    ``E_i(x)`` the rate models encode;
  * ``fold_energy`` reproduces the scalar add-point update on both banked
    backends;
  * the makespan/energy Pareto front — thresholds, caps and metrics are
    computed host-side, so the numpy and jax fronts (times, energies AND
    allocations) must agree bit-for-bit, with the scalar backend matching
    allocation-for-allocation;
  * front endpoints equal the PURE time-/energy-objective partitions
    exactly, times strictly increase and energies strictly decrease along
    the front, and ``objective="time"`` stays bit-identical to a store with
    no energy attached (the do-no-harm lock);
  * ``capped_energy_partition`` allocations respect the time threshold's
    reachable set and infeasible thresholds return None, never raise.

Lanes follow the repo convention: 200-case numpy-rng lanes under ``slow``,
tier-1 smoke versions always on, a hypothesis lane through ``_hyp``.
"""

import numpy as np
import pytest

from _hyp import given, settings, st

import jax

from repro.core import PiecewiseLinearFPM, Scheduler, SpeedStore
from repro.core.energy import (
    ParetoFront,
    capped_energy_partition,
    energy_model,
    pareto_front,
)
from repro.core.modelbank import ModelBank

BIT_EXACT = jax.default_backend() == "cpu"

BACKENDS = ("scalar", "numpy", "jax")


# ---------------------------------------------------------------------------
# Case generation: heterogeneous speed + affine energy, all rows non-empty
# ---------------------------------------------------------------------------


def _case_from_raw(speed_rows, energy_params, n, caps_frac, min_units):
    models = [PiecewiseLinearFPM.from_points(r) for r in speed_rows]
    xs = sorted({x for r in speed_rows for x, _ in r})
    emods = [
        energy_model([(x, a + b * x) for x in xs]) for a, b in energy_params
    ]
    return dict(
        models=models, emods=emods, energy_params=energy_params,
        n=n, caps_frac=caps_frac, min_units=min_units,
    )


def _random_case(rng):
    # p and the knot count are drawn from small fixed sets so the jax
    # lane's [T, p, k] programs amortize across cases (one compile per
    # shape, same policy as test_modelbank_jax's K_PAD padding)
    p = int(rng.choice([3, 5]))
    grid = np.sort(rng.uniform(1.0, 1e4, 5))
    rows = []
    for i in range(p):
        ss = rng.uniform(0.5, 500.0, len(grid))
        rows.append(list(zip(grid.tolist(), ss.tolist())))
    # heterogeneous energy efficiency: per-proc affine E(x) = a + b x with
    # b spread over ~40x, so time- and energy-optimal partitions differ and
    # the front is non-degenerate for most draws (degenerate draws still
    # exercise the single-point-front path)
    energy_params = [
        (float(rng.uniform(1.0, 50.0)), float(rng.uniform(0.05, 2.0)))
        for _ in range(p)
    ]
    n = int(rng.integers(max(2 * p, 8), 3000))
    caps_frac = rng.uniform(0.6, 1.0, p).tolist() if rng.random() < 0.4 else None
    min_units = int(rng.integers(0, 2))
    return _case_from_raw(rows, energy_params, n, caps_frac, min_units)


def _caps(case):
    if case["caps_frac"] is None:
        return None
    lo = max(1, case["min_units"])
    return [lo + int(f * case["n"]) for f in case["caps_frac"]]


def _stores(case):
    out = {}
    for backend in BACKENDS:
        st_ = SpeedStore.from_models(case["models"], backend=backend)
        st_.attach_energy(
            [PiecewiseLinearFPM.from_points(m.as_points()) for m in case["emods"]]
        )
        out[backend] = st_
    return out


def _scalar_energy(case, d):
    """Ground-truth total energy of an allocation through the SCALAR rate
    models (interpolation happens in rate space, so off-grid energies are
    model-predicted, not affine — the affine law is exact only at knots)."""
    return sum(
        float(m.time(float(di)))
        for m, di in zip(case["emods"], d)
        if di > 0
    )


# ---------------------------------------------------------------------------
# The parity checkers (one description drives every lane)
# ---------------------------------------------------------------------------


def _check_energy_query_parity(case):
    stores = _stores(case)
    p = len(case["models"])
    rng = np.random.default_rng(int(case["n"]))
    d = rng.integers(0, 200, p).astype(np.float64)
    ref = np.asarray(
        [float(m.time(float(di))) if di > 0 else np.nan
         for m, di in zip(case["emods"], d)]
    )
    vals = {b: np.asarray(stores[b].energy_at(d), dtype=np.float64)
            for b in BACKENDS}
    for b in BACKENDS:
        act = d > 0
        assert np.allclose(vals[b][act], ref[act], rtol=1e-9), b
    # at the knots the affine law E(x) = a + b x is exact
    grid = sorted({x for m in case["models"] for x, _ in m.as_points()})
    x0 = float(grid[0])
    at_knot = np.asarray(stores["numpy"].energy_at(np.full(p, x0)))
    want = np.asarray([a + b * x0 for a, b in case["energy_params"]])
    assert np.allclose(at_knot, want, rtol=1e-9)
    if BIT_EXACT:
        np.testing.assert_array_equal(vals["numpy"], vals["jax"])
    fe = {b: stores[b].fleet_energy(d) for b in BACKENDS}
    assert fe["numpy"] == fe["scalar"]
    if BIT_EXACT:
        assert fe["numpy"] == fe["jax"]


def _check_objective_time_unchanged(case):
    """The do-no-harm lock: attaching energy and passing objective="time"
    must not move a single unit on any backend."""
    caps = _caps(case)
    for backend in BACKENDS:
        plain = SpeedStore.from_models(case["models"], backend=backend)
        d0, t0 = plain.partition(case["n"], caps, min_units=case["min_units"])
        st_ = _stores(case)[backend]
        d1, t1 = st_.partition(
            case["n"], caps, min_units=case["min_units"], objective="time"
        )
        assert d1 == d0 and t1 == t0, backend


def _check_front_parity(case):
    stores = _stores(case)
    caps = _caps(case)
    n, mu = case["n"], case["min_units"]
    fronts = {
        b: stores[b].pareto_front(n, caps, min_units=mu, num_points=9)
        for b in BACKENDS
    }
    for b, f in fronts.items():
        assert isinstance(f, ParetoFront) and len(f) >= 1, b
        # every front point is a valid partition
        for d in f.allocations:
            assert int(d.sum()) == n
            if caps is not None:
                assert all(int(v) <= c for v, c in zip(d, caps))
            assert all(int(v) >= mu for v in d)
        # strict bi-objective monotonicity
        assert all(f.times[i] < f.times[i + 1] for i in range(len(f) - 1)), b
        assert all(
            f.energies[i] > f.energies[i + 1] for i in range(len(f) - 1)
        ), b
        # endpoints ARE the pure solutions
        d_time, _ = stores[b].partition(n, caps, min_units=mu)
        assert list(f.allocations[0]) == d_time, b
        d_energy, _ = stores[b].partition(n, caps, min_units=mu, objective="energy")
        if len(f) > 1:
            assert list(f.allocations[-1]) == d_energy, b
        # reported energies match the affine ground truth
        for d, e in zip(f.allocations, f.energies):
            assert np.isclose(e, _scalar_energy(case, d), rtol=1e-9), b
    # the numpy and jax fronts are the same object bit-for-bit
    fa, fb = fronts["numpy"], fronts["scalar"]
    np.testing.assert_array_equal(fa.allocations, fb.allocations)
    if BIT_EXACT:
        fj = fronts["jax"]
        np.testing.assert_array_equal(fa.times, fj.times)
        np.testing.assert_array_equal(fa.energies, fj.energies)
        np.testing.assert_array_equal(fa.allocations, fj.allocations)


def _check_capped_partition(case):
    stores = _stores(case)
    sbank = ModelBank.from_models(case["models"])
    ebank = ModelBank.from_models(case["emods"])
    caps = _caps(case)
    n, mu = case["n"], case["min_units"]
    front = stores["numpy"].pareto_front(n, caps, min_units=mu, num_points=7)
    icaps = [n] * len(case["models"]) if caps is None else caps
    t_lo, t_hi = float(front.times[0]), float(front.times[-1])
    # at (and beyond) the slow end every threshold is feasible
    d = capped_energy_partition(
        sbank, ebank, n, icaps, t_hi * 1.5, floor_d=front.allocations[0],
        min_units=mu,
    )
    assert d is not None and sum(d) == n
    assert all(v <= c for v, c in zip(d, icaps))
    # an absurdly tight threshold without a floor is infeasible -> None
    assert (
        capped_energy_partition(sbank, ebank, n, icaps, t_lo * 1e-6, min_units=mu)
        is None
    )


def _check_fold_energy_parity(case):
    p = len(case["models"])
    rng = np.random.default_rng(int(case["n"]) + 1)
    obs = [
        (rng.uniform(1.0, 1e4, p), rng.uniform(1.0, 1e3, p))
        for _ in range(3)
    ]
    queries = rng.uniform(1.0, 1e4, p)
    vals = {}
    for backend in ("numpy", "jax"):
        st_ = SpeedStore.from_models(case["models"], backend=backend)
        for x, e in obs:
            st_.fold_energy(x, e)
        vals[backend] = np.asarray(st_.energy_at(queries), dtype=np.float64)
    assert np.all(np.isfinite(vals["numpy"]))
    if BIT_EXACT:
        np.testing.assert_array_equal(vals["numpy"], vals["jax"])


def _check_all(case):
    _check_energy_query_parity(case)
    _check_objective_time_unchanged(case)
    _check_front_parity(case)
    _check_capped_partition(case)
    _check_fold_energy_parity(case)


# ---------------------------------------------------------------------------
# Tier-1 smokes + slow fuzz lanes
# ---------------------------------------------------------------------------


def test_energy_parity_smoke(rng):
    with jax.enable_x64():
        for _ in range(25):
            _check_all(_random_case(rng))


@pytest.mark.slow
def test_energy_parity_fuzz_lane():
    rng = np.random.default_rng(42)
    with jax.enable_x64():
        for _ in range(200):
            _check_all(_random_case(rng))


@st.composite
def _hyp_cases(draw):
    p = draw(st.integers(min_value=2, max_value=6))
    k = draw(st.integers(min_value=3, max_value=6))
    grid = sorted(
        set(
            draw(
                st.lists(
                    st.floats(min_value=1.0, max_value=1e4,
                              allow_nan=False, allow_infinity=False),
                    min_size=k, max_size=k,
                )
            )
        )
    ) or [1.0]
    rows = [
        list(zip(grid, draw(st.lists(
            st.floats(min_value=0.5, max_value=500.0,
                      allow_nan=False, allow_infinity=False),
            min_size=len(grid), max_size=len(grid)))))
        for _ in range(p)
    ]
    energy_params = [
        (
            draw(st.floats(min_value=1.0, max_value=50.0,
                           allow_nan=False, allow_infinity=False)),
            draw(st.floats(min_value=0.05, max_value=2.0,
                           allow_nan=False, allow_infinity=False)),
        )
        for _ in range(p)
    ]
    n = draw(st.integers(min_value=max(2 * p, 8), max_value=2000))
    return _case_from_raw(rows, energy_params, n, None, 0)


@pytest.mark.slow
@given(case=_hyp_cases())
@settings(max_examples=200, deadline=None)
def test_energy_parity_fuzz_hypothesis(case):
    with jax.enable_x64():
        _check_all(case)


# ---------------------------------------------------------------------------
# Unit behaviour: front picking, validation, persistence, scheduler dispatch
# ---------------------------------------------------------------------------


def _simple_case():
    rng = np.random.default_rng(5)
    return _random_case(rng)


def test_pareto_pick_and_knee():
    f = ParetoFront(
        times=np.asarray([1.0, 2.0, 4.0]),
        energies=np.asarray([30.0, 20.0, 10.0]),
        allocations=np.asarray([[3, 1], [2, 2], [1, 3]], dtype=np.int64),
    )
    assert f.pick(None) == f.knee()
    assert f.pick(25.0) == 1  # fastest point within budget
    assert f.pick(10.0) == 2
    assert f.pick(5.0) == 2  # unattainable budget -> best effort (last)
    assert f.pick(1e9) == 0
    d = f.as_dict()
    assert d["times"] == [1.0, 2.0, 4.0] and len(d["allocations"]) == 3


def test_energy_model_validation():
    with pytest.raises(ValueError):
        energy_model([(0.0, 5.0)])
    with pytest.raises(ValueError):
        energy_model([(10.0, -1.0)])
    m = energy_model([(10.0, 5.0), (20.0, 8.0)])
    # rate representation: time(x) under the rate model IS E(x)
    assert np.isclose(m.time(10.0), 5.0) and np.isclose(m.time(20.0), 8.0)


def test_attach_energy_validation():
    case = _simple_case()
    st_ = SpeedStore.from_models(case["models"], backend="numpy")
    with pytest.raises(ValueError, match="energy models"):
        st_.attach_energy(case["emods"][:-1])
    with pytest.raises(ValueError, match="need energy models"):
        st_.partition(case["n"], objective="energy")
    with pytest.raises(ValueError, match="no energy models"):
        st_.pareto_front(case["n"])
    st_.attach_energy(case["emods"])
    with pytest.raises(ValueError, match="objective"):
        st_.partition(case["n"], objective="power")


def test_state_dict_roundtrips_energy():
    case = _simple_case()
    st_ = _stores(case)["numpy"]
    state = st_.state_dict()
    assert "energy_points" in state
    st2 = SpeedStore.from_state(state)
    assert st2.has_energy
    f1 = st_.pareto_front(case["n"], num_points=5)
    f2 = st2.pareto_front(case["n"], num_points=5)
    np.testing.assert_array_equal(f1.allocations, f2.allocations)
    # a plain store's state has no energy field and loads clean
    plain = SpeedStore.from_models(case["models"], backend="numpy")
    assert "energy_points" not in plain.state_dict()
    assert not SpeedStore.from_state(plain.state_dict()).has_energy


def test_scheduler_objective_dispatch():
    case = _simple_case()
    caps = _caps(case)
    sched = Scheduler(
        SpeedStore.from_models(case["models"], backend="numpy"),
        backend="numpy", n_units=case["n"],
    )
    d_time = sched.partition(caps=caps).allocations
    sched.attach_energy(case["emods"])
    assert sched.partition(caps=caps, objective="time").allocations == d_time
    front = sched.pareto_front(caps=caps)  # dispatch uses the default grid
    knee = front.knee()
    part = sched.partition(caps=caps, objective="pareto")
    assert part.allocations == [int(v) for v in front.allocations[knee]]
    capped = sched.partition(
        caps=caps, energy_cap=float(front.energies[0]) * 0.999
    )
    idx = front.pick(float(front.energies[0]) * 0.999)
    assert capped.allocations == [int(v) for v in front.allocations[idx]]
    # state round-trip carries the energy models
    sched2 = Scheduler.from_state(sched.state_dict())
    assert sched2.store.has_energy


def test_scheduler_objective_needs_energy_and_flat_mode():
    case = _simple_case()
    sched = Scheduler(
        SpeedStore.from_models(case["models"], backend="numpy"),
        backend="numpy", n_units=case["n"],
    )
    with pytest.raises(ValueError, match="need energy models"):
        sched.partition(objective="energy")
    p = len(case["models"])
    hier = Scheduler(
        SpeedStore.from_models(case["models"], backend="numpy"),
        backend="numpy", n_units=case["n"], groups=[i % 2 for i in range(p)],
    )
    hier.store.attach_energy(case["emods"])
    with pytest.raises(ValueError, match="objective"):
        hier.partition(objective="energy")
