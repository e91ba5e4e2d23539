"""Scheduler facade: parity with the legacy free functions across all three
backends and all four policies, the online lifecycle, full-fidelity state
round-trips, and analytic sample-and-bank.

This file (with ``test_scheduler_shims.py``) runs in CI under
``-W error::DeprecationWarning``: everything the facade does internally must
be warning-free — new code cannot sneak back onto the shimmed legacy API.
Legacy calls made *for comparison* are wrapped in :func:`legacy`.
"""

import contextlib
import warnings

import numpy as np
import pytest

import jax

from repro.core import (
    AnalyticModel,
    HCL_SPECS,
    Partition,
    Policy,
    Scheduler,
    SimulatedExecutor,
    SpeedStore,
    imbalance,
    make_hcl_time_fns,
    sample_analytic_points,
    speed_fn_2d,
)
from repro.core.fpm import PiecewiseLinearFPM


@contextlib.contextmanager
def legacy():
    """Run a deliberately-deprecated legacy call without tripping the
    ``-W error::DeprecationWarning`` CI lane."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        yield


def _fleet(p, seed=0):
    rng = np.random.default_rng(seed)
    models = []
    for _ in range(p):
        k = int(rng.integers(2, 7))
        xs = np.sort(rng.uniform(1.0, 1e4, k))
        ss = rng.uniform(0.5, 500.0, k)
        models.append(PiecewiseLinearFPM.from_points(list(zip(xs, ss))))
    return models


def _row_fns(tfns, n):
    return [(lambda tf: lambda r: tf(r * n))(tf) for tf in tfns]


# ---------------------------------------------------------------------------
# SpeedStore: one resolution, three backends, legacy-identical partitions
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["scalar", "numpy", "jax"])
def test_speedstore_partition_matches_legacy(backend):
    models = _fleet(6, seed=3)
    n, caps, mu = 1234, [400, 500, 300, 600, 200, 400], 2
    if backend == "jax":
        with jax.enable_x64():
            store = SpeedStore.from_models(models, backend="jax")
            assert store.backend == "jax"
            got = store.partition_units(n, caps, min_units=mu)
            with legacy():
                from repro.core import partition_units

                want = partition_units(models, n, caps, min_units=mu, backend="jax")
    else:
        store = SpeedStore.from_models(models, backend=backend)
        assert store.backend == backend
        got = store.partition_units(n, caps, min_units=mu)
        with legacy():
            from repro.core import partition_units

            want = partition_units(
                models, n, caps, min_units=mu, vectorize=(backend != "scalar")
            )
    assert got == want
    assert sum(got) == n


def test_speedstore_backend_resolved_once():
    models = _fleet(4)
    auto = SpeedStore.from_models(models)
    assert auto.backend == "numpy"  # piecewise -> banked
    analytic = SpeedStore.from_models([AnalyticModel(lambda x: x / 5.0)] * 3)
    assert analytic.backend == "scalar"  # no piecewise representation
    forced = SpeedStore.from_models(models, backend="scalar")
    assert forced.backend == "scalar"
    # requesting a banked backend for unbankable models falls back, once
    fb = SpeedStore.from_models([AnalyticModel(lambda x: x / 5.0)] * 3, backend="numpy")
    assert fb.backend == "scalar"


def test_speedstore_query_protocol():
    models = _fleet(5, seed=9)
    store = SpeedStore.from_models(models)
    x = np.array([10.0, 50.0, 100.0, 5.0, 2000.0])
    np.testing.assert_allclose(
        store.speeds(x), [m.speed(float(v)) for m, v in zip(models, x)]
    )
    np.testing.assert_allclose(
        store.times(x), [m.time(float(v)) for m, v in zip(models, x)]
    )
    caps = np.full(5, 1e4)
    np.testing.assert_allclose(
        store.alloc_at_time(0.5, caps),
        [m.alloc_at_time(0.5, 1e4) for m in models],
    )


def test_speedstore_fold_in_updates_models():
    store = SpeedStore.empty(3)
    store.fold_in([10.0, 20.0, 30.0], [1.0, 2.0, 3.0], [True, False, True])
    assert store.num_points == [1, 0, 1]
    assert store.models[0].as_points() == [(10.0, 1.0)]
    assert store.models[2].as_points() == [(30.0, 3.0)]


def test_speedstore_infeasible_raises_all_backends():
    models = _fleet(4)
    for backend in ("scalar", "numpy"):
        store = SpeedStore.from_models(models, backend=backend)
        with pytest.raises(ValueError, match="min_units"):
            store.partition_units(3, min_units=1)  # min_units * p > n
        with pytest.raises(ValueError, match="min_units"):
            store.partition_units(20, caps=[0, 20, 20, 20], min_units=1)
        with pytest.raises(ValueError, match="infeasible"):
            store.partition_units(100, caps=[10, 10, 10, 10])


# ---------------------------------------------------------------------------
# Policy parity: the facade reproduces every legacy policy entry point
# ---------------------------------------------------------------------------


def test_policy_cpm_matches_legacy():
    speeds = [1.0, 2.0, 3.0, 2.5]
    part = Scheduler.from_speeds(speeds).partition(600)
    with legacy():
        from repro.core import cpm_partition

        want = cpm_partition(speeds, 600)
    assert part.allocations == want
    assert part.policy is Policy.CPM
    assert part.d == part.allocations  # legacy-friendly alias


def test_policy_ffmpa_matches_legacy():
    n = 2048
    _, tfns = make_hcl_time_fns(n)
    rows = _row_fns(tfns, n)
    models = [AnalyticModel(tf) for tf in rows]
    part = Scheduler.from_models(models, policy=Policy.FFMPA).partition(n, min_units=1)
    with legacy():
        from repro.core import partition_units

        want = partition_units([AnalyticModel(tf) for tf in rows], n, min_units=1)
    assert part.allocations == want
    assert part.t_star is not None and part.t_star > 0
    assert part.makespan == pytest.approx(max(part.times))


def test_policy_dfpa_matches_legacy():
    n = 2048
    _, tfns = make_hcl_time_fns(n)
    rows = _row_fns(tfns, n)
    part = Scheduler().autotune(SimulatedExecutor(time_fns=rows), n, 0.025, min_units=1)
    with legacy():
        from repro.core import dfpa

        res = dfpa(SimulatedExecutor(time_fns=rows), n, 0.025, min_units=1)
    assert part.allocations == res.d
    assert part.iterations == res.iterations
    assert part.converged == res.converged
    assert part.imbalance == pytest.approx(res.imbalance, rel=1e-12)
    assert [h[0] for h in part.diagnostics["history"]] == [h[0] for h in res.history]


def test_policy_grid2d_matches_legacy():
    p, q, M, N = 3, 3, 256, 256
    specs = HCL_SPECS[: p * q]
    grid = [[speed_fn_2d(specs[i * q + j]) for j in range(q)] for i in range(p)]
    part = Scheduler(grid=grid, policy=Policy.GRID2D).partition_grid(M, N, eps=0.1)
    with legacy():
        from repro.core import cpm_partition_2d, dfpa_partition_2d, ffmpa_partition_2d

        want = dfpa_partition_2d(grid, M, N, eps=0.1)
        cpm_want, cpm_cost = cpm_partition_2d(grid, M, N)
        ff_want = ffmpa_partition_2d(grid, M, N, eps=0.1)
    assert part.col_widths == want.col_widths
    assert part.row_heights == want.row_heights
    assert part.iterations == want.outer_iterations
    assert part.diagnostics["bench_cost"] == pytest.approx(want.bench_cost)
    # the flat allocations view is the column-major row flatten
    assert part.allocations == [r for col in part.row_heights for r in col]

    cpm_part = Scheduler(grid=grid, policy=Policy.CPM).partition_grid(M, N)
    assert cpm_part.col_widths == cpm_want.col_widths
    assert cpm_part.row_heights == cpm_want.row_heights
    assert cpm_part.diagnostics["bench_cost"] == pytest.approx(cpm_cost)

    ff_part = Scheduler(grid=grid, policy=Policy.FFMPA).partition_grid(
        M, N, eps=0.1, max_outer=50
    )
    assert ff_part.col_widths == ff_want.col_widths
    assert ff_part.row_heights == ff_want.row_heights


def test_grid2d_jax_backend_matches_numpy():
    p, q, M = 3, 2, 128
    rng = np.random.default_rng(5)
    widths = [40, 44]
    fpms = [[PiecewiseLinearFPM() for _ in range(q)] for _ in range(p)]
    fpm_width = [[None] * q for _ in range(p)]
    for i in range(p):
        for j in range(q):
            for r in rng.uniform(4, M, 4):
                fpms[i][j].add_point(float(r), float(rng.uniform(1.0, 30.0)))
            fpm_width[i][j] = widths[j]
    rows_np = Scheduler(policy=Policy.GRID2D).repartition_grid(
        fpms, fpm_width, widths, M
    )
    with jax.enable_x64():
        rows_jax = Scheduler(policy=Policy.GRID2D, backend="jax").repartition_grid(
            fpms, fpm_width, widths, M
        )
    assert rows_np == rows_jax
    assert all(sum(r) == M for r in rows_np)


# ---------------------------------------------------------------------------
# The online lifecycle: observe / repartition / join / leave / stragglers
# ---------------------------------------------------------------------------


def test_observe_rebalances_like_balance_controller():
    speeds = [1.0, 2.0, 3.0, 2.0]

    def drive(obj):
        trace = []
        for _ in range(20):
            times = [d / s if d > 0 else 0.0 for d, s in zip(obj.d, speeds)]
            obj.observe(times)
            trace.append(list(obj.d))
        return trace

    sched = Scheduler(n_units=64, num_groups=4, eps=0.08, min_units=1, smooth=1.0)
    with legacy():
        from repro.runtime.balance import BalanceController

        ctrl = BalanceController(n_units=64, num_groups=4, eps=0.08, smooth=1.0)
        want = drive(ctrl)
    got = drive(sched)
    assert got == want
    assert sched.rebalances == ctrl.rebalances


def test_repartition_returns_partition():
    sched = Scheduler(n_units=60, num_groups=3, eps=0.05, min_units=1, smooth=1.0)
    for _ in range(6):
        times = [d / s if d > 0 else 0.0 for d, s in zip(sched.d, [1.0, 2.0, 3.0])]
        sched.observe(times)
    part = sched.repartition()
    assert isinstance(part, Partition)
    assert sum(part.allocations) == 60
    assert part.backend == "numpy"
    assert part.t_star is not None


def test_per_call_caps_are_one_shot():
    """Regression: per-call ``caps`` used to overwrite ``self.caps`` and
    silently constrain every later repartition/observe/autotune in the
    session.  They are one-shot now; ``persist_caps=True`` opts back in."""
    models = _fleet(4, seed=11)

    sched = Scheduler(SpeedStore.from_models(models), n_units=60, min_units=1)
    free = sched.partition().allocations
    hot = int(np.argmax(free))  # cap the busiest processor so it binds
    caps = [100] * 4
    caps[hot] = 1
    assert free[hot] > 1
    capped = sched.partition(caps=caps).allocations
    assert capped[hot] == 1
    assert sched.caps is None  # session state untouched
    assert sched.repartition().allocations == free  # failing before the fix

    sticky = Scheduler(SpeedStore.from_models(models), n_units=60, min_units=1)
    assert sticky.partition(caps=caps, persist_caps=True).allocations[hot] == 1
    assert sticky.caps == caps
    assert sticky.repartition().allocations[hot] == 1

    # construction-time caps still persist (they are session state)
    sess = Scheduler(
        SpeedStore.from_models(models), n_units=60, min_units=1, caps=caps
    )
    assert sess.partition().allocations[hot] == 1
    assert sess.repartition().allocations[hot] == 1


def test_join_leave_lifecycle():
    sched = Scheduler(n_units=60, num_groups=3, eps=0.05, min_units=1, smooth=1.0)
    for _ in range(12):
        times = [d / s if d > 0 else 0.0 for d, s in zip(sched.d, [1.0, 2.0, 3.0])]
        sched.observe(times)
    pts_before = sched.models[0].num_points
    sched.leave(2)
    assert sched.num_groups == 2
    assert sum(sched.d) == 60
    assert sched.models[0].num_points == pts_before  # survivors keep points
    sched.join(1)
    assert sched.num_groups == 3
    assert sum(sched.d) == 60
    assert sched.models[2].num_points == 1  # donor-seeded newcomer
    assert sched.d[2] > 0  # not starved


def test_resize_matches_legacy_elastic():
    def build():
        s = Scheduler(n_units=60, num_groups=3, eps=0.05, min_units=1, smooth=1.0)
        for _ in range(10):
            times = [d / sp if d > 0 else 0.0 for d, sp in zip(s.d, [1.0, 2.0, 3.0])]
            s.observe(times)
        return s

    sched = build()
    new = sched.resize([0, 2], joined=1, caps=None)
    with legacy():
        from repro.runtime.balance import BalanceController
        from repro.runtime.elastic import elastic_rebalance

        ctrl = BalanceController(
            n_units=60, num_groups=3, eps=0.05, smooth=1.0,
            models=[PiecewiseLinearFPM.from_points(m.as_points()) for m in sched.models],
            d=list(sched.d),
        )
        want = elastic_rebalance(ctrl, surviving=[0, 2], joined=1)
    assert new.d == want.d
    assert [m.as_points() for m in new.models] == [m.as_points() for m in want.models]


def test_straggler_actions_auto_reprofile():
    from repro.runtime.straggler import StragglerAction, StragglerDetector

    sched = Scheduler(
        n_units=40, num_groups=2, eps=0.05, min_units=1, smooth=1.0,
        detector=StragglerDetector(factor=1.5, patience=2, patience_hard=99),
    )
    sched.observe([2.0, 1.0])
    sched.observe([d / 2.0 for d in sched.d])
    pts_before = sched.models[0].num_points
    assert pts_before >= 1
    # group 0 suddenly 4x slower than its model predicts -> strikes -> reprofile
    healthy = [m.time(d) for m, d in zip(sched.models, sched.d)]
    acts = []
    for _ in range(3):
        acts.append(sched.straggler_actions([healthy[0] * 4.0, healthy[1]]))
    assert any(a[0] is StragglerAction.REPROFILE for a in acts)
    assert sched.models[0].num_points <= 1  # estimate invalidated


# ---------------------------------------------------------------------------
# State round-trip: full config, bit-identical next-round allocations
# ---------------------------------------------------------------------------


def _drive_rounds(sched, speeds, rounds=3):
    ds = []
    for _ in range(rounds):
        times = [d / s if d > 0 else 0.0 for d, s in zip(sched.d, speeds)]
        sched.observe(times)
        ds.append(list(sched.d))
    return ds


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_state_roundtrip_bit_identical_next_round(backend):
    """Regression for the legacy ``BalanceController.from_state`` kwarg
    asymmetry: ``state_dict`` now carries backend/smooth/eps/min_units/caps
    AND the EMA state, so a restored scheduler's next rounds are
    bit-identical to the uninterrupted run."""
    ctx = contextlib.nullcontext()
    if backend == "jax":
        ctx = jax.enable_x64()
    speeds = [4.0, 3.0, 1.5, 2.0]
    with ctx:
        sched = Scheduler(
            n_units=64, num_groups=4, eps=0.03, min_units=1, smooth=0.7,
            caps=[40, 40, 40, 40], backend=backend,
        )
        _drive_rounds(sched, speeds, rounds=4)
        state = sched.state_dict()

        restored = Scheduler.from_state(state)
        assert restored.backend == backend
        assert restored.smooth == sched.smooth
        assert restored.eps == sched.eps
        assert restored.min_units == sched.min_units
        assert restored.caps == sched.caps
        assert restored.d == sched.d
        assert restored._ema == sched._ema

        want = _drive_rounds(sched, speeds, rounds=3)
        got = _drive_rounds(restored, speeds, rounds=3)
    assert got == want


def test_balance_controller_state_carries_full_config():
    """The legacy wrapper's state now round-trips backend and smooth too."""
    with legacy():
        from repro.runtime.balance import BalanceController

        ctrl = BalanceController(
            n_units=32, num_groups=2, eps=0.2, min_units=1, smooth=0.9
        )
        ctrl.observe([2.0, 1.0])
        state = ctrl.state_dict()
        assert state["smooth"] == 0.9
        assert state["backend"] == "numpy"
        back = BalanceController.from_state(state)
        assert back.eps == 0.2
        assert back.smooth == 0.9
        assert back.d == ctrl.d
        assert back._ema == ctrl._ema


# ---------------------------------------------------------------------------
# Analytic sample-and-bank (ROADMAP: FFMPA baselines on the vectorized path)
# ---------------------------------------------------------------------------


def test_sample_analytic_points_hits_tolerance():
    m = AnalyticModel(lambda x: x / (50.0 + 10.0 * np.log1p(x)))  # smooth speed
    pts = sample_analytic_points(m, hi=5000.0, tol=0.005)
    fit = PiecewiseLinearFPM.from_points(pts)
    for x in np.geomspace(1.0, 5000.0, 64):
        assert fit.speed(float(x)) == pytest.approx(m.speed(float(x)), rel=0.02)


def test_analytic_models_ride_the_bank_path():
    n = 2048
    _, tfns = make_hcl_time_fns(n)
    rows = _row_fns(tfns, n)
    models = [AnalyticModel(tf) for tf in rows]
    store = SpeedStore.from_models(
        models, analytic_tol=0.002, analytic_hi=float(n), analytic_max_points=256
    )
    assert store.backend == "numpy"  # sampled -> banked, no scalar fallback
    d_bank = store.partition_units(n, min_units=1)
    with legacy():
        from repro.core import partition_units

        d_exact = partition_units([AnalyticModel(tf) for tf in rows], n, min_units=1)
    assert sum(d_bank) == n
    # sampled models approximate the analytic oracle: near-identical makespan
    ms_bank = max(tf(d) for tf, d in zip(rows, d_bank))
    ms_exact = max(tf(d) for tf, d in zip(rows, d_exact))
    assert ms_bank <= ms_exact * 1.02
    imb = imbalance([tf(d) for tf, d in zip(rows, d_bank) if d > 0])
    assert imb <= 0.05


def test_grid_ffmpa_sample_and_bank_close_to_scalar():
    p, q, M, N = 3, 3, 192, 192
    specs = HCL_SPECS[: p * q]
    grid = [[speed_fn_2d(specs[i * q + j]) for j in range(q)] for i in range(p)]
    exact = Scheduler(grid=grid, policy=Policy.FFMPA).partition_grid(M, N, eps=0.1, max_outer=50)
    banked = Scheduler(grid=grid, policy=Policy.FFMPA, analytic_tol=0.005).partition_grid(
        M, N, eps=0.1, max_outer=50
    )
    from repro.core import app_time_2d

    t_exact = app_time_2d(grid, exact, K=N)
    t_banked = app_time_2d(grid, banked, K=N)
    assert t_banked <= t_exact * 1.05


# ---------------------------------------------------------------------------
# Construction / validation
# ---------------------------------------------------------------------------


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="backend"):
        Scheduler(backend="Jax")
    with pytest.raises(ValueError, match="backend"):
        SpeedStore.from_models(_fleet(2), backend="cuda")


def test_partition_requires_units_or_grid():
    with pytest.raises(ValueError, match="n_units"):
        Scheduler(num_groups=2).partition()
    with pytest.raises(ValueError, match="grid"):
        Scheduler(num_groups=2).partition_grid(8, 8)
