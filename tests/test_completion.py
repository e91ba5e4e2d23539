"""Threshold-count completion: the adversarial-bank battery + fuzz parity.

The threshold-count integer completion (see the "completion modes" section
in ``core/modelbank.py``) is exact ONLY on monotone-time banks — per-unit
time ``x / s(x)`` nondecreasing in ``x`` — where the per-unit greedy
provably processes unit increments in globally sorted ``(time, -rem, index)``
order.  This suite locks the two safety properties that make routing it by
default safe:

  * **demotion** — adversarial banks (speed spikes, duplicate-``x`` rows
    whose replacing speed jumps up, non-positive hand-built points) are
    detected by the host-side monotonicity check and provably fall back to
    the exact per-unit loop (monkeypatched-kernel proofs below, on both
    banked backends);
  * **parity** — on monotone banks the threshold-count completion produces
    makespans (and, on the CPU x64 contract, allocations) bit-identical to
    the per-unit heap/argmin completion across the numpy bank, the jitted
    jax bank, and the stacked ``[q, p, k]`` 2-D path, and both modes raise
    identical ``ValueError`` s on infeasible inputs.

Fuzz lanes follow the repo convention: a hypothesis lane through the
optional ``tests/_hyp.py`` shim plus an always-on numpy-rng lane, >= 200
cases each, both driving the same ``_check_*`` functions; the heavy lanes
carry the ``slow`` marker (tier-1 runs the 25-case smoke versions).
"""

import numpy as np
import pytest

from _hyp import given, settings, st

import jax

from repro.core import PiecewiseLinearFPM, Scheduler, SpeedStore
from repro.core.modelbank import ModelBank
from repro.core.modelbank_jax import JaxModelBank
from repro.core.partition import (
    _partition_units_bank,
    _prep_unit_caps,
    _threshold_prefill_bank,
)
from repro.core import modelbank_jax as mbj
from repro.core import partition as partition_mod

BIT_EXACT = jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# Bank generators
# ---------------------------------------------------------------------------


def _bank(rows) -> ModelBank:
    """Rows of ``[(x, s), ...]`` point lists -> a padded bank."""
    return ModelBank.from_point_lists(
        [([x for x, _ in r], [s for _, s in r]) for r in rows]
    )


def _monotone_rows(rng, p, k_max=7):
    """Random monotone-time rows, two flavours: nonincreasing speed, and
    increasing-speed-but-ordered-knot-times (s may rise as long as x/s does
    not fall) — the subtle class the flag must still accept."""
    rows = []
    for _ in range(p):
        k = int(rng.integers(1, k_max))
        xs = np.unique(rng.uniform(1.0, 1e4, k))
        if rng.random() < 0.5:
            ss = np.sort(rng.uniform(0.5, 500.0, len(xs)))[::-1]
        else:
            ts = np.sort(rng.uniform(0.1, 50.0, len(xs)))
            ss = xs / ts
        rows.append(list(zip(xs.tolist(), ss.tolist())))
    return rows


def _spike_models(p=4):
    """Speed spike at large x: time DIPS (10/5=2 -> 20/50=0.4) — the
    canonical adversarial bank the flag must demote."""
    return [
        PiecewiseLinearFPM.from_points([(10.0, 5.0), (20.0, 50.0)])
        for _ in range(p)
    ]


def _makespan(bank: ModelBank, d) -> float:
    return float(np.max(bank.time(np.asarray(d, dtype=np.float64))))


# ---------------------------------------------------------------------------
# The monotonicity flag: classification
# ---------------------------------------------------------------------------


def test_flag_accepts_monotone_classes():
    rng = np.random.default_rng(0)
    for _ in range(50):
        bank = _bank(_monotone_rows(rng, 6))
        assert bank.is_monotone()
    # constant models (single point) are trivially monotone
    assert ModelBank.from_point_lists([([1.0], [5.0])] * 3).is_monotone()
    # empty rows are vacuously monotone
    assert ModelBank.from_point_lists([([], [])]).is_monotone()


def test_flag_rejects_speed_spike():
    bank = ModelBank.from_models(_spike_models())
    assert not bank.is_monotone()


def test_flag_duplicate_x_rows():
    # hand-built duplicate-x pair: speed jumping UP at the same x makes time
    # jump DOWN -> non-monotone; jumping down keeps time nondecreasing.
    assert not ModelBank.from_point_lists([([10.0, 10.0], [5.0, 9.0])]).is_monotone()
    assert ModelBank.from_point_lists([([10.0, 10.0], [9.0, 5.0])]).is_monotone()


def test_flag_rejects_nonpositive_and_nonfinite_points():
    assert not ModelBank.from_point_lists([([10.0, 20.0], [5.0, 0.0])]).is_monotone()
    assert not ModelBank.from_point_lists([([10.0, 20.0], [5.0, -1.0])]).is_monotone()
    assert not ModelBank.from_point_lists([([0.0, 20.0], [5.0, 4.0])]).is_monotone()
    assert not ModelBank.from_point_lists(
        [([10.0, 20.0], [5.0, float("inf")])]
    ).is_monotone()


def test_flag_scaled_propagation():
    rng = np.random.default_rng(1)
    bank = _bank(_monotone_rows(rng, 4))
    assert bank.is_monotone()
    assert bank.scaled([2.0, 0.5, 1.0, 3.0]).monotone is True
    # non-positive scale resets the cached flag to unknown
    assert bank.scaled([2.0, -0.5, 1.0, 3.0]).monotone is None


def test_flag_jax_mirrors_numpy_and_survives_fold_in():
    rng = np.random.default_rng(2)
    with jax.enable_x64():
        good = _bank(_monotone_rows(rng, 5))
        bad = ModelBank.from_models(_spike_models())
        assert JaxModelBank.from_bank(good).is_monotone() == good.is_monotone()
        assert JaxModelBank.from_bank(bad).is_monotone() == bad.is_monotone()
        # device-side check (flag unknown after construction without a host
        # bank) agrees with the host check
        jb = JaxModelBank(
            xs=np.asarray(bad.xs), ss=np.asarray(bad.ss), counts=np.asarray(bad.counts)
        )
        assert jb.monotone is None
        assert jb.is_monotone() is False
        # fold_in resets the flag; the lazy recompute sees the new points:
        # a monotone carry turns non-monotone when a speed spike folds in
        jb2 = JaxModelBank.from_bank(good)
        assert jb2.is_monotone()
        spike_s = np.asarray(good.ss).max() * 1e6
        jb2 = jb2.fold_in(np.full(5, 2e4), np.full(5, spike_s))
        assert jb2.monotone is None
        assert jb2.is_monotone() is False
        # ... and a duplicate-x replace can HEAL a violation
        jb_bad = JaxModelBank.from_bank(
            ModelBank.from_point_lists([([10.0, 20.0], [5.0, 50.0])])
        )
        assert jb_bad.is_monotone() is False
        healed = jb_bad.fold_in([20.0], [6.0])  # replace the spike speed
        assert healed.is_monotone() is True


def test_flag_stack_combination():
    rng = np.random.default_rng(3)
    with jax.enable_x64():
        good = JaxModelBank.from_bank(
            _bank(_monotone_rows(rng, 4))
        )
        bad = JaxModelBank.from_bank(ModelBank.from_models(_spike_models(4)))
        good.is_monotone(), bad.is_monotone()
        assert JaxModelBank.stack([good, good]).monotone is True
        assert JaxModelBank.stack([good, bad]).monotone is False
        unknown = good.copy()
        unknown.monotone = None
        st = JaxModelBank.stack([good, unknown])
        assert st.monotone is None
        assert st.is_monotone() is True  # lazy device check resolves it


# ---------------------------------------------------------------------------
# Demotion proofs: non-monotone banks provably take the exact loop
# ---------------------------------------------------------------------------


def test_numpy_auto_keeps_greedy_even_on_monotone_banks(monkeypatch):
    """Monkeypatch the threshold kernel to explode: on the numpy HOST path
    "auto" must never reach it — monotone bank or not — because the lazy
    heap was never the host bottleneck and the threshold pass costs ~one
    extra continuous solve there (the ROADMAP PR 4 niggle).  Only an
    explicit completion="threshold" engages the kernel."""

    def boom(*a, **k):  # pragma: no cover - reaching it IS the assertion
        raise AssertionError("threshold completion engaged")

    monkeypatch.setattr(partition_mod, "_threshold_prefill_bank", boom)
    bad = ModelBank.from_models(_spike_models())
    icaps = _prep_unit_caps(4, 37, None, 1)
    d, _ = _partition_units_bank(bad, 37, list(icaps), min_units=1)  # no raise
    assert sum(d) == 37
    rng = np.random.default_rng(4)
    good = _bank(_monotone_rows(rng, 4))
    d, _ = _partition_units_bank(good, 37, list(icaps), min_units=1)  # no raise
    assert sum(d) == 37
    with pytest.raises(AssertionError, match="threshold completion engaged"):
        _partition_units_bank(
            good, 37, list(icaps), min_units=1, completion="threshold"
        )


def test_jax_auto_demotes_nonmonotone_to_exact(monkeypatch):
    """Spy on the jitted kernel's static completion flag: False for the
    adversarial bank, True for the monotone one."""
    real = mbj._partition_units_jit
    seen = []

    def spy(*args, **kw):
        seen.append(bool(kw.get("completion_fast", False)))
        return real(*args, **kw)

    monkeypatch.setattr(mbj, "_partition_units_jit", spy)
    rng = np.random.default_rng(5)
    with jax.enable_x64():
        bad = JaxModelBank.from_bank(ModelBank.from_models(_spike_models()))
        d = bad.partition_units(37, min_units=1)
        assert int(np.asarray(d).sum()) == 37
        good = JaxModelBank.from_bank(
            _bank(_monotone_rows(rng, 4))
        )
        good.partition_units(37, min_units=1)
    assert seen == [False, True]


def test_nonmonotone_auto_equals_forced_greedy():
    """Demoted adversarial banks produce exactly the per-unit result."""
    bad = ModelBank.from_models(_spike_models())
    icaps = _prep_unit_caps(4, 55, None, 1)
    d_auto, t_auto = _partition_units_bank(bad, 55, list(icaps), min_units=1)
    d_greedy, t_greedy = _partition_units_bank(
        bad, 55, list(icaps), min_units=1, completion="greedy"
    )
    assert d_auto == d_greedy and t_auto == t_greedy
    with jax.enable_x64():
        jb = JaxModelBank.from_bank(bad)
        d_jax = jb.partition_units(55, min_units=1)
        d_jax_g = jb.partition_units(55, min_units=1, completion="greedy")
    assert np.array_equal(np.asarray(d_jax), np.asarray(d_jax_g))
    if BIT_EXACT:
        assert list(map(int, d_jax)) == d_greedy


# ---------------------------------------------------------------------------
# Fast/exact raise identically on infeasible inputs
# ---------------------------------------------------------------------------


def _infeasible_variants(p, n):
    return [
        dict(n=2 * p - 1, caps=None, min_units=2),  # min_units * p > n
        dict(n=n, caps=[0] + [n] * (p - 1), min_units=1),  # cap < min_units
        dict(n=n, caps=[max(n // (2 * p) - 1, 0)] * p, min_units=0),  # sum < n
    ]


@pytest.mark.parametrize("completion", ["threshold", "greedy", "auto"])
def test_infeasible_raises_identically_both_modes(completion):
    rng = np.random.default_rng(6)
    bank = _bank(_monotone_rows(rng, 5))
    store = SpeedStore.from_bank(bank)
    with jax.enable_x64():
        jb = JaxModelBank.from_bank(bank)
        for kw in _infeasible_variants(5, 200):
            with pytest.raises(ValueError):
                store.partition_units(
                    kw["n"], kw["caps"], min_units=kw["min_units"],
                    completion=completion,
                )
            with pytest.raises(ValueError):
                jb.partition_units(
                    kw["n"], kw["caps"], min_units=kw["min_units"],
                    completion=completion,
                )


def test_cap_below_min_units_raises_under_threshold():
    """The silent min_units-shortfall regression, re-locked for the fast
    path: caps[i] < min_units refuses loudly in every completion mode."""
    rng = np.random.default_rng(7)
    bank = _bank(_monotone_rows(rng, 4))
    store = SpeedStore.from_bank(bank)
    with jax.enable_x64():
        jb = JaxModelBank.from_bank(bank)
        for completion in ("threshold", "greedy", "auto"):
            with pytest.raises(ValueError, match="min_units"):
                store.partition_units(
                    20, [1, 20, 20, 20], min_units=2, completion=completion
                )
            with pytest.raises(ValueError, match="min_units"):
                jb.partition_units(
                    20, [1, 20, 20, 20], min_units=2, completion=completion
                )


def test_empty_model_positive_cap_raises_under_threshold():
    bank = ModelBank.from_point_lists([([], []), ([10.0], [5.0])])
    assert bank.is_monotone()  # vacuously — the raise must still fire
    store = SpeedStore.from_bank(bank)
    with pytest.raises(ValueError):
        store.partition_units(10, completion="threshold")
    with jax.enable_x64():
        with pytest.raises(ValueError):
            JaxModelBank.from_bank(bank).partition_units(10, completion="threshold")


def test_unknown_completion_mode_rejected_everywhere():
    rng = np.random.default_rng(8)
    bank = _bank(_monotone_rows(rng, 3))
    with pytest.raises(ValueError, match="completion"):
        _partition_units_bank(bank, 30, [30] * 3, min_units=0, completion="fast")
    with pytest.raises(ValueError, match="completion"):
        SpeedStore.from_bank(bank).partition_units(30, completion="fast")
    with jax.enable_x64():
        with pytest.raises(ValueError, match="completion"):
            JaxModelBank.from_bank(bank).partition_units(30, completion="fast")
    with pytest.raises(ValueError, match="completion"):
        Scheduler(SpeedStore.from_bank(bank), completion="fast")


def test_scalar_backend_refuses_threshold():
    store = SpeedStore.from_models(
        [PiecewiseLinearFPM.from_points([(10.0, 5.0)])] * 3, backend="scalar"
    )
    with pytest.raises(ValueError, match="scalar"):
        store.partition_units(30, completion="threshold")
    # auto and greedy stay on the exact loop without complaint
    assert sum(store.partition_units(30)) == 30
    assert sum(store.partition_units(30, completion="greedy")) == 30


# ---------------------------------------------------------------------------
# Deterministic edges: zero caps, min_units take-back, leftover == 0
# ---------------------------------------------------------------------------


def test_zero_caps_fast_equals_exact():
    rng = np.random.default_rng(9)
    bank = _bank(_monotone_rows(rng, 6))
    caps = [0, 40, 0, 40, 40, 40]
    icaps = _prep_unit_caps(6, 100, caps, 0)
    d_t, _ = _partition_units_bank(
        bank, 100, list(icaps), min_units=0, completion="threshold"
    )
    d_g, _ = _partition_units_bank(
        bank, 100, list(icaps), min_units=0, completion="greedy"
    )
    assert d_t == d_g
    assert d_t[0] == d_t[2] == 0
    assert sum(d_t) == 100


def test_min_units_takeback_path_unaffected_by_completion():
    """When min_units overshoots n the take-back runs and leftover hits 0 —
    the completion (either mode) must be a no-op."""
    rng = np.random.default_rng(10)
    bank = _bank(_monotone_rows(rng, 5))
    icaps = _prep_unit_caps(5, 5, None, 1)
    d_t, _ = _partition_units_bank(
        bank, 5, list(icaps), min_units=1, completion="threshold"
    )
    d_g, _ = _partition_units_bank(
        bank, 5, list(icaps), min_units=1, completion="greedy"
    )
    assert d_t == d_g
    assert sum(d_t) == 5 and all(di >= 1 for di in d_t)


# ---------------------------------------------------------------------------
# Fuzz parity: fast == exact on monotone banks, all three backends
# ---------------------------------------------------------------------------


def _random_monotone_case(rng):
    p = int(rng.integers(1, 9))
    rows = _monotone_rows(rng, p)
    n = int(rng.integers(max(2 * p, 4), 3000))
    min_units = int(rng.integers(0, 3))
    lo = max(1, min_units)
    caps = [lo + int(f * n) for f in rng.uniform(0.0, 1.0, p)]
    if min_units == 0 and p > 1 and rng.random() < 0.3:
        caps[int(rng.integers(0, p))] = 0  # zero-cap row
    return dict(rows=rows, n=n, caps=caps, min_units=min_units)


def _check_completion_parity(case, *, with_jax=True):
    rows, n, caps, min_units = (
        case["rows"], case["n"], case["caps"], case["min_units"],
    )
    p = len(rows)
    if sum(min(c, n) for c in caps) < n:
        return  # infeasible: the raise-parity property's subject
    bank = _bank(rows)
    assert bank.is_monotone()
    icaps = _prep_unit_caps(p, n, caps, min_units)
    d_exact, t_exact = _partition_units_bank(
        bank, n, list(icaps), min_units=min_units, completion="greedy"
    )
    # the host path's "auto" is greedy by design, so the numpy threshold
    # kernel is fuzz-locked by FORCING it; "auto" stays the jax routing.
    d_fast, t_fast = _partition_units_bank(
        bank, n, list(icaps), min_units=min_units, completion="threshold"
    )
    assert sum(d_fast) == n
    assert all(min_units <= di <= ci for di, ci in zip(d_fast, icaps))
    assert t_fast == t_exact
    # the headline contract: bit-identical makespans (allocations agree too
    # on every case ever generated; the makespan is the guaranteed metric)
    assert _makespan(bank, d_fast) == _makespan(bank, d_exact)
    assert d_fast == d_exact
    if not with_jax:
        return
    with jax.enable_x64():
        jb = JaxModelBank.from_bank(bank)
        d_jax_fast = jb.partition_units(n, caps, min_units=min_units)
        d_jax_exact = jb.partition_units(
            n, caps, min_units=min_units, completion="greedy"
        )
    assert int(np.asarray(d_jax_fast).sum()) == n
    assert _makespan(bank, np.asarray(d_jax_fast)) == _makespan(bank, d_exact)
    assert _makespan(bank, np.asarray(d_jax_exact)) == _makespan(bank, d_exact)
    if BIT_EXACT:
        assert list(map(int, d_jax_fast)) == d_fast
        assert list(map(int, d_jax_exact)) == d_exact


def test_completion_parity_smoke():
    """Tier-1 smoke: 25 cases through all backends."""
    rng = np.random.default_rng(1001)
    for _ in range(25):
        _check_completion_parity(_random_monotone_case(rng))


@pytest.mark.slow
def test_completion_parity_fuzz_numpy_lane():
    rng = np.random.default_rng(1002)
    for _ in range(200):
        _check_completion_parity(_random_monotone_case(rng))


@st.composite
def _monotone_cases(draw):
    p = draw(st.integers(min_value=1, max_value=8))
    rows = []
    for _ in range(p):
        k = draw(st.integers(min_value=1, max_value=6))
        xs = sorted(
            set(
                draw(
                    st.lists(
                        st.floats(min_value=1.0, max_value=1e4,
                                  allow_nan=False, allow_infinity=False),
                        min_size=k, max_size=k,
                    )
                )
            )
        )
        ts = sorted(
            draw(
                st.lists(
                    st.floats(min_value=0.1, max_value=50.0,
                              allow_nan=False, allow_infinity=False),
                    min_size=len(xs), max_size=len(xs),
                )
            )
        )
        rows.append([(x, x / t) for x, t in zip(xs, ts)])
    n = draw(st.integers(min_value=max(2 * p, 4), max_value=3000))
    min_units = draw(st.integers(min_value=0, max_value=2))
    lo = max(1, min_units)
    caps = [
        lo + int(f * n)
        for f in draw(
            st.lists(st.floats(min_value=0.0, max_value=1.0),
                     min_size=p, max_size=p)
        )
    ]
    return dict(rows=rows, n=n, caps=caps, min_units=min_units)


@pytest.mark.slow
@given(case=_monotone_cases())
@settings(max_examples=200, deadline=None)
def test_completion_parity_fuzz_hypothesis(case):
    _check_completion_parity(case, with_jax=False)


@pytest.mark.slow
@given(case=_monotone_cases())
@settings(max_examples=200, deadline=None)
def test_completion_parity_fuzz_hypothesis_jax(case):
    _check_completion_parity(case, with_jax=True)


# ---------------------------------------------------------------------------
# Stacked [q, p, k] path: threshold completion per column
# ---------------------------------------------------------------------------


def test_stacked_threshold_matches_per_column_exact():
    rng = np.random.default_rng(1003)
    q, p, n = 5, 6, 700
    cols = [_monotone_rows(rng, p, k_max=6) for _ in range(q)]
    with jax.enable_x64():
        banks = [
            JaxModelBank.from_bank(_bank(c)) for c in cols
        ]
        stacked = JaxModelBank.stack(banks)
        assert stacked.monotone is True
        d_fast = stacked.partition_units(n, min_units=1)  # auto -> threshold
        ns = np.array([n + 41 * j for j in range(q)])
        d_var = stacked.partition_units(ns, min_units=1)
    for j in range(q):
        cb = _bank(cols[j])
        icaps = _prep_unit_caps(p, n, None, 1)
        want, _ = _partition_units_bank(
            cb, n, list(icaps), min_units=1, completion="greedy"
        )
        assert _makespan(cb, np.asarray(d_fast[j])) == _makespan(cb, want)
        if BIT_EXACT:
            assert list(map(int, d_fast[j])) == want
        icaps_v = _prep_unit_caps(p, int(ns[j]), None, 1)
        want_v, _ = _partition_units_bank(
            cb, int(ns[j]), list(icaps_v), min_units=1, completion="greedy"
        )
        if BIT_EXACT:
            assert list(map(int, d_var[j])) == want_v


def test_stacked_with_one_adversarial_column_demotes_only_itself():
    """One spiky column demotes only its OWN lane to the exact loop: the
    per-column ``monotone_lanes`` routing keeps the monotone column on the
    threshold bulk grant while the adversarial one takes the per-unit loop,
    in the same device program; results must equal the per-column exact
    partitions either way."""
    rng = np.random.default_rng(1004)
    p, n = 4, 300
    good = _monotone_rows(rng, p)
    bad = [[(10.0, 5.0), (20.0, 50.0)] for _ in range(p)]
    with jax.enable_x64():
        banks = [
            JaxModelBank.from_bank(_bank(c))
            for c in (good, bad)
        ]
        stacked = JaxModelBank.stack(banks)
        assert stacked.monotone is False
        assert list(stacked.monotone_lanes()) == [True, False]
        d = stacked.partition_units(n, min_units=1)
    for j, c in enumerate((good, bad)):
        cb = _bank(c)
        icaps = _prep_unit_caps(p, n, None, 1)
        want, _ = _partition_units_bank(
            cb, n, list(icaps), min_units=1, completion="greedy"
        )
        if BIT_EXACT:
            assert list(map(int, d[j])) == want


# ---------------------------------------------------------------------------
# Scheduler/SpeedStore routing + the dtype policy
# ---------------------------------------------------------------------------


def test_scheduler_completion_knob_round_trips():
    rng = np.random.default_rng(1005)
    bank = _bank(_monotone_rows(rng, 4))
    sched = Scheduler(
        SpeedStore.from_models(bank.to_models()), n_units=120, completion="greedy"
    )
    part = sched.partition()
    state = sched.state_dict()
    assert state["completion"] == "greedy"
    restored = Scheduler.from_state(state)
    assert restored.completion == "greedy"
    assert restored.partition().allocations == part.allocations
    # auto and greedy agree on a monotone bank through the facade too
    auto = Scheduler(SpeedStore.from_models(bank.to_models()), n_units=120)
    assert auto.partition().allocations == part.allocations


def test_scheduler_threshold_knob_demotes_scalar_stores():
    """The session knob is uniform across paths: 'threshold' on a
    scalar-backed store demotes to the exact loop instead of raising (the
    strict refusal stays on the direct SpeedStore API)."""
    models = [PiecewiseLinearFPM.from_points([(10.0, 5.0 + i)]) for i in range(3)]
    store = SpeedStore.from_models(
        [PiecewiseLinearFPM.from_points(m.as_points()) for m in models],
        backend="scalar",
    )
    sched = Scheduler(store, n_units=60, completion="threshold")
    part = sched.partition()  # no raise: demoted via _completion_for
    assert sum(part.allocations) == 60
    with pytest.raises(ValueError, match="scalar"):
        store.partition_units(60, completion="threshold")


def test_scheduler_state_dict_round_trips_dtype():
    """A float32-store scheduler must restore as a float32 scheduler —
    dtype is part of the full-fidelity persistence contract."""
    models = [
        PiecewiseLinearFPM.from_points([(10.0, 5.0 + i), (100.0, 4.0 + i)])
        for i in range(4)
    ]
    with jax.enable_x64():
        sched = Scheduler(
            SpeedStore.from_models(models, backend="jax", dtype=np.float32),
            n_units=120,
        )
        state = sched.state_dict()
        assert state["dtype"] == "float32"
        restored = Scheduler.from_state(state)
        assert str(restored.store.device_bank(snapshot=False).dtype) == "float32"
        assert restored.partition().allocations == sched.partition().allocations


def _serving_fleet(p: int, seed: int = 0):
    """Heterogeneous monotone fleet shaped like the benchmark's (plateau
    spanning ~3x, cache boost at small x, paging decay past a knee)."""
    rng = np.random.default_rng(seed)
    plateau = rng.uniform(1.0, 3.0, p) * 1e6
    knee = rng.uniform(2e3, 2e4, p)
    rows = []
    for i in range(p):
        xs = np.geomspace(16.0, 8.0 * knee[i], 6)
        ss = np.where(
            xs <= knee[i],
            plateau[i] * (1.0 + 0.4 * np.exp(-xs / 500.0)),
            plateau[i] / (1.0 + 2.0 * (xs - knee[i]) / knee[i]),
        )
        rows.append(list(zip(xs.tolist(), ss.tolist())))
    return rows


@pytest.mark.slow
def test_float32_store_allocations_match_float64_at_p_10k():
    """The ROADMAP dtype decision, locked: a float32 device bank partitions
    a p=10^4 serving fleet (n=10^6) identically to the float64 reference
    (the zero-drift result quantified by the jax_f32_* benchmark columns)."""
    p = 10_000
    n = 100 * p
    bank = _bank(_serving_fleet(p, seed=p))
    assert bank.is_monotone()
    with jax.enable_x64():
        s64 = SpeedStore.from_jax_bank(JaxModelBank.from_bank(bank))
        s32 = SpeedStore.from_jax_bank(
            JaxModelBank.from_bank(bank, dtype=np.float32)
        )
        assert str(s32.device_bank(snapshot=False).dtype) == "float32"
        d64 = s64.partition_units(n, min_units=1)
        d32 = s32.partition_units(n, min_units=1)
    assert d64 == d32
    if BIT_EXACT:
        icaps = _prep_unit_caps(p, n, None, 1)
        d_np, _ = _partition_units_bank(bank, n, list(icaps), min_units=1)
        assert d64 == d_np


def test_float32_store_construction_and_state_round_trip():
    models = [
        PiecewiseLinearFPM.from_points([(10.0, 5.0 + i), (100.0, 4.0 + i)])
        for i in range(4)
    ]
    with jax.enable_x64():
        s32 = SpeedStore.from_models(models, backend="jax", dtype=np.float32)
        assert str(s32.device_bank(snapshot=False).dtype) == "float32"
        d32 = s32.partition_units(200, min_units=1)
        state = s32.state_dict()
        assert state["dtype"] == "float32"
        back = SpeedStore.from_state(state)
        assert str(back.device_bank(snapshot=False).dtype) == "float32"
        assert back.partition_units(200, min_units=1) == d32
        d64 = SpeedStore.from_models(
            [PiecewiseLinearFPM.from_points(m.as_points()) for m in models],
            backend="jax",
        ).partition_units(200, min_units=1)
        # fold_in keeps the policy dtype on the carry
        s32.fold_in([50.0] * 4, [4.5] * 4)
        assert str(s32._carry().dtype) == "float32"
    assert d32 == d64


# ---------------------------------------------------------------------------
# The prefill invariant (white-box): strict bracket leaves >= 1 unit for
# the exact tie-break pass
# ---------------------------------------------------------------------------


def test_prefill_strict_bracket_leaves_boundary_remainder():
    rng = np.random.default_rng(1006)
    for _ in range(50):
        p = int(rng.integers(2, 9))
        bank = _bank(_monotone_rows(rng, p))
        n = int(rng.integers(2 * p, 1500))
        caps = np.full(p, n, dtype=np.int64)
        from repro.core.partition import _continuous_bank

        xs, t_star, _ = _continuous_bank(bank, float(n), [float(n)] * p)
        d0 = np.minimum(np.floor(np.asarray(xs)).astype(np.int64), caps)
        leftover = n - int(d0.sum())
        if leftover <= 0:
            continue
        g, rem = _threshold_prefill_bank(bank, d0, caps, leftover, t_star)
        assert rem >= 1  # count(lo) < leftover is strict
        assert int(g.sum()) - int(d0.sum()) == leftover - rem
        assert np.all(g >= d0) and np.all(g <= caps)
