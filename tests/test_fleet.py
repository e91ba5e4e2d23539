"""FleetScheduler: the multi-job batched parity battery + profile registry.

The fleet contract is BIT-IDENTITY: one ``FleetScheduler`` driving q jobs
through its stacked-bank lock-step rounds must produce, for every job,
exactly what q independent ``Scheduler.autotune`` loops would have —
allocations, measured times, round histories, convergence verdicts, bench
costs AND the folded FPM estimates.  That holds through mid-flight
``admit``/``retire``, mixed per-job ``n``/``eps``/``caps``/``min_units``,
and adversarial non-monotone jobs (whose lanes demote to the exact per-unit
completion without touching their neighbours' threshold routing).

Fuzz lanes follow the repo convention: an always-on numpy-rng lane plus a
hypothesis lane through the optional ``tests/_hyp.py`` shim, >= 200 cases
each under the ``slow`` marker, with small smoke versions in tier-1.

The registry suite locks the persistence satellite: a warm start from a
saved registry reproduces the donor session's next-round allocations
bit-identically, and corrupt/missing registries degrade to a cold start
with a warning, never a crash.
"""

import json

import numpy as np
import pytest

from _hyp import given, settings, st

import jax

from repro.core import (
    BatchedSimulatedExecutor2D,
    PiecewiseLinearFPM,
    Policy,
    Scheduler,
    SimulatedExecutor,
    SpeedStore,
)
from repro.core import modelbank_jax as mbj
from repro.core.scheduler import _even
from repro.fleet import FleetScheduler, JobSpec, ProfileRegistry

BIT_EXACT = jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# Ground-truth fleets: per-(job, proc) time functions, scalar + batched views
# ---------------------------------------------------------------------------


def _knee_params(rng, q, p):
    base = rng.uniform(1e-4, 2e-3, (q, p))
    knee = rng.uniform(5.0, 80.0, (q, p))
    return base, knee


def _knee_time(base, knee, x):
    t = x * base
    return t + np.where(x > knee, (x - knee) * base * 4.0, 0.0)


def _scalar_fns(base, knee, j):
    """Job j's per-processor scalar time fns (for SimulatedExecutor)."""
    return [
        (lambda b, k: lambda x: float(_knee_time(b, k, float(x))))(
            base[j, i], knee[j, i]
        )
        for i in range(base.shape[1])
    ]


def _batch_fn(base, knee):
    """The same fns as one [q, p] array op (for BatchedSimulatedExecutor2D).
    Identical float64 arithmetic to the scalar fns, so times are bit-equal."""

    def fn(X):
        return _knee_time(base, knee, X)

    return fn


def _dip_fns(p, K=30.0):
    """Adversarial job: time DROPS 10x past K, so observed speed jumps up
    and the job's FPM bank turns non-monotone — its lane must demote to the
    exact per-unit completion.  Per-proc base speeds span 8x so the DFPA
    allocations straddle K and the dip is actually observed."""
    a = np.asarray([1e-3 * (2.0**i) for i in range(p)])
    scalar = [
        (lambda ai: lambda x: float(ai * x if x < K else 0.1 * ai * x))(a[i])
        for i in range(p)
    ]

    def batch_row(x_row):
        return np.where(x_row < K, a * x_row, 0.1 * a * x_row)

    return scalar, batch_row


# ---------------------------------------------------------------------------
# The parity checker: fleet rounds vs q independent Scheduler.autotune loops
# ---------------------------------------------------------------------------


def _random_fleet_case(rng):
    p = int(rng.integers(2, 7))
    q = int(rng.integers(1, 5))
    base, knee = _knee_params(rng, q, p)
    jobs = []
    for j in range(q):
        n = int(rng.integers(max(2 * p, 8), 60 * p))
        min_units = int(rng.integers(0, 2))
        caps = None
        if rng.random() < 0.4:
            lo = max(1, min_units)
            # each cap >= 0.6 n keeps every case feasible at p >= 2
            caps = [lo + int(f * n) for f in rng.uniform(0.6, 1.0, p)]
        jobs.append(
            dict(
                n=n,
                eps=float(rng.uniform(0.02, 0.25)),
                caps=caps,
                min_units=min_units,
                max_iter=int(rng.integers(3, 12)),
            )
        )
    return dict(p=p, q=q, base=base, knee=knee, jobs=jobs)


def _independent_results(case, backend):
    """q separate Scheduler.autotune sessions — the reference trajectories."""
    p, base, knee = case["p"], case["base"], case["knee"]
    out = []
    for j, kw in enumerate(case["jobs"]):
        ex = SimulatedExecutor(time_fns=_scalar_fns(base, knee, j))
        sched = Scheduler(SpeedStore.empty(p, backend=backend), backend=backend)
        res = sched.autotune(
            ex,
            kw["n"],
            kw["eps"],
            max_iter=kw["max_iter"],
            caps=kw["caps"],
            min_units=kw["min_units"],
        )
        out.append(
            dict(
                res=res,
                cost=ex.total_cost,
                points=[m.as_points() for m in sched.store.models],
            )
        )
    return out


def _fleet_results(case, backend):
    p, q, base, knee = case["p"], case["q"], case["base"], case["knee"]
    fleet = FleetScheduler(p, backend=backend)
    for j, kw in enumerate(case["jobs"]):
        fleet.admit(
            JobSpec(
                name=str(j),
                n=kw["n"],
                eps=kw["eps"],
                caps=kw["caps"],
                min_units=kw["min_units"],
                max_iter=kw["max_iter"],
            )
        )
    ex = BatchedSimulatedExecutor2D(
        time_fn_batch_2d=_batch_fn(base, knee),
        p=p,
        q=q,
        job_names=[str(j) for j in range(q)],
    )
    results = fleet.run(ex)
    return fleet, results


def _assert_job_parity(ref, part, cost, points):
    res = ref["res"]
    assert part.allocations == res.allocations
    assert part.times == res.times
    assert part.iterations == res.iterations
    assert part.converged == res.converged
    assert part.imbalance == res.imbalance
    assert part.diagnostics["history"] == res.diagnostics["history"]
    assert cost == ref["cost"]
    assert points == ref["points"]


def _check_fleet_parity(case, backend):
    indep = _independent_results(case, backend)
    fleet, results = _fleet_results(case, backend)
    for j in range(case["q"]):
        name = str(j)
        _assert_job_parity(
            indep[j],
            results[name],
            fleet.bench_cost(name),
            [m.as_points() for m in fleet.models(name)],
        )
    # the tentpole economics: one partition + one fold program per round,
    # regardless of q (vs 2q for the sequential loops)
    if backend == "jax":
        assert fleet.device_dispatches <= 2 * fleet.rounds


# ---------------------------------------------------------------------------
# Deterministic parity + the dispatch-count contract
# ---------------------------------------------------------------------------


def test_fleet_parity_three_jobs_jax():
    rng = np.random.default_rng(100)
    case = _random_fleet_case(rng)
    with jax.enable_x64():
        _check_fleet_parity(case, "jax")


def test_fleet_parity_numpy_backend():
    rng = np.random.default_rng(101)
    for _ in range(5):
        _check_fleet_parity(_random_fleet_case(rng), "numpy")


def test_fleet_parity_scalar_backend():
    """The seed scalar loop is a first-class fleet backend too (the 2-D
    grid driver inherits whatever backend the Scheduler session was built
    with, including 'scalar')."""
    rng = np.random.default_rng(105)
    for _ in range(3):
        _check_fleet_parity(_random_fleet_case(rng), "scalar")


def test_partition_grid_scalar_backend_still_works():
    """Regression: routing _grid_dfpa through the fleet driver must not
    drop the scalar backend the Scheduler facade accepts."""
    from repro.core import HCL_SPECS, speed_fn_2d

    specs = HCL_SPECS[:4]
    grid = [[speed_fn_2d(specs[i * 2 + j]) for j in range(2)] for i in range(2)]
    part = Scheduler(grid=grid, policy=Policy.GRID2D, backend="scalar").partition_grid(
        64, 64, eps=0.2
    )
    assert sum(part.col_widths) == 64
    for rows in part.row_heights:
        assert sum(rows) == 64


def test_fleet_parity_smoke_fuzz_jax():
    """Tier-1 jax smoke: 6 random fleets through the full parity checker."""
    rng = np.random.default_rng(102)
    with jax.enable_x64():
        for _ in range(6):
            _check_fleet_parity(_random_fleet_case(rng), "jax")


@pytest.mark.slow
def test_fleet_parity_fuzz_numpy_lane():
    rng = np.random.default_rng(103)
    for _ in range(200):
        _check_fleet_parity(_random_fleet_case(rng), "numpy")


@pytest.mark.slow
def test_fleet_parity_fuzz_jax_lane():
    """200 fuzzed fleets on the stacked device path (shapes kept small so
    the jit cache amortizes across cases)."""
    rng = np.random.default_rng(104)
    with jax.enable_x64():
        for _ in range(200):
            case = _random_fleet_case(rng)
            _check_fleet_parity(case, "jax")


@st.composite
def _fleet_cases(draw):
    p = draw(st.integers(min_value=2, max_value=5))
    q = draw(st.integers(min_value=1, max_value=3))
    base = np.asarray(
        draw(
            st.lists(
                st.lists(
                    st.floats(min_value=1e-4, max_value=2e-3,
                              allow_nan=False, allow_infinity=False),
                    min_size=p, max_size=p,
                ),
                min_size=q, max_size=q,
            )
        )
    )
    knee = np.asarray(
        draw(
            st.lists(
                st.lists(
                    st.floats(min_value=5.0, max_value=80.0,
                              allow_nan=False, allow_infinity=False),
                    min_size=p, max_size=p,
                ),
                min_size=q, max_size=q,
            )
        )
    )
    jobs = []
    for _ in range(q):
        n = draw(st.integers(min_value=max(2 * p, 8), max_value=60 * p))
        min_units = draw(st.integers(min_value=0, max_value=1))
        jobs.append(
            dict(
                n=n,
                eps=draw(st.floats(min_value=0.02, max_value=0.25)),
                caps=None,
                min_units=min_units,
                max_iter=draw(st.integers(min_value=3, max_value=10)),
            )
        )
    return dict(p=p, q=q, base=base, knee=knee, jobs=jobs)


@pytest.mark.slow
@given(case=_fleet_cases())
@settings(max_examples=200, deadline=None)
def test_fleet_parity_fuzz_hypothesis(case):
    _check_fleet_parity(case, "numpy")


# ---------------------------------------------------------------------------
# Mid-flight admit / retire
# ---------------------------------------------------------------------------


def test_admit_mid_flight_matches_independent():
    """A job admitted at fleet round k runs exactly the autotune loop it
    would have run in its own session — lock-stepping with strangers (and
    the restack its admission forces) must not perturb anyone."""
    rng = np.random.default_rng(200)
    p, q = 4, 3
    base, knee = _knee_params(rng, q, p)
    specs = [
        JobSpec(name=str(j), n=40 + 30 * j, eps=0.05, min_units=1, max_iter=8)
        for j in range(q)
    ]
    case = dict(
        p=p, q=q, base=base, knee=knee,
        jobs=[
            dict(n=s.n, eps=s.eps, caps=None, min_units=1, max_iter=8)
            for s in specs
        ],
    )
    with jax.enable_x64():
        indep = _independent_results(case, "jax")
        fleet = FleetScheduler(p, backend="jax")
        ex = BatchedSimulatedExecutor2D(
            time_fn_batch_2d=_batch_fn(base, knee), p=p, q=q,
            job_names=[str(j) for j in range(q)],
        )
        fleet.admit(specs[0])
        fleet.step(ex)
        fleet.step(ex)
        fleet.admit(specs[1])  # mid-flight; restack next round
        fleet.step(ex)
        fleet.admit(specs[2])
        results = fleet.run(ex)
    for j in range(q):
        name = str(j)
        _assert_job_parity(
            indep[j], results[name], fleet.bench_cost(name),
            [m.as_points() for m in fleet.models(name)],
        )


def test_retire_mid_flight_prefix_and_survivors():
    """Retiring a running job returns its best-so-far Partition whose
    history is a prefix of the independent run's; survivors are unaffected
    bit-for-bit."""
    rng = np.random.default_rng(201)
    p, q = 4, 3
    base, knee = _knee_params(rng, q, p)
    case = dict(
        p=p, q=q, base=base, knee=knee,
        jobs=[
            dict(n=50 + 40 * j, eps=1e-6, caps=None, min_units=1, max_iter=9)
            for j in range(q)
        ],
    )
    with jax.enable_x64():
        indep = _independent_results(case, "jax")
        fleet = FleetScheduler(p, backend="jax")
        for j in range(q):
            kw = case["jobs"][j]
            fleet.admit(
                JobSpec(name=str(j), n=kw["n"], eps=kw["eps"], min_units=1,
                        max_iter=kw["max_iter"])
            )
        ex = BatchedSimulatedExecutor2D(
            time_fn_batch_2d=_batch_fn(base, knee), p=p, q=q,
            job_names=[str(j) for j in range(q)],
        )
        for _ in range(3):
            fleet.step(ex)
        retired = fleet.retire("1")
        assert "1" not in fleet.jobs
        results = fleet.run(ex)
    full = indep[1]["res"].diagnostics["history"]
    got = retired.diagnostics["history"]
    assert got == full[: len(got)] and 0 < len(got) <= 3
    for j in (0, 2):
        _assert_job_parity(
            indep[j], results[str(j)], fleet.bench_cost(str(j)),
            [m.as_points() for m in fleet.models(str(j))],
        )


def test_resize_equals_warm_readmission():
    """resize(n') keeps the estimates and restarts the loop — bit-identical
    to retiring the job and re-admitting it warm-started from the same
    models with the new n."""
    rng = np.random.default_rng(202)
    p = 4
    base, knee = _knee_params(rng, 1, p)
    ex = BatchedSimulatedExecutor2D(
        time_fn_batch_2d=_batch_fn(base, knee), p=p, q=1, job_names=["a"]
    )
    with jax.enable_x64():
        fleet = FleetScheduler(p, backend="jax")
        fleet.admit(JobSpec(name="a", n=60, eps=0.03, min_units=1, max_iter=4))
        fleet.run(ex)
        snapshot = [
            PiecewiseLinearFPM.from_points(m.as_points()) for m in fleet.models("a")
        ]
        fleet.resize("a", n=100)
        res_resized = fleet.run(ex)["a"]

        fleet2 = FleetScheduler(p, backend="jax")
        fleet2.admit(
            JobSpec(name="a", n=100, eps=0.03, min_units=1, max_iter=4),
            models=snapshot,
        )
        res_fresh = fleet2.run(ex)["a"]
    assert res_resized.allocations == res_fresh.allocations
    assert res_resized.diagnostics["history"] == res_fresh.diagnostics["history"]
    assert sum(res_resized.allocations) == 100


def test_rebalance_drops_stale_result_and_reports_live_view():
    """After a converged tenant's load drifts, rebalance() must not keep
    serving the old cached Partition: snapshot() reports the live (new-n)
    distribution."""
    rng = np.random.default_rng(203)
    p = 4
    base, knee = _knee_params(rng, 1, p)
    ex = BatchedSimulatedExecutor2D(
        time_fn_batch_2d=_batch_fn(base, knee), p=p, q=1, job_names=["a"]
    )
    with jax.enable_x64():
        fleet = FleetScheduler(p, backend="jax")
        fleet.admit(JobSpec(name="a", n=60, eps=0.3, min_units=1, max_iter=6))
        fleet.run(ex)
        assert fleet.result("a").converged
        d_new = fleet.rebalance({"a": 120})["a"]
    assert sum(d_new) == 120
    snap = fleet.snapshot("a")
    assert snap.allocations == d_new and sum(snap.allocations) == 120
    with pytest.raises(ValueError, match="not finished"):
        fleet.result("a")


# ---------------------------------------------------------------------------
# Adversarial non-monotone job: demotes only its own lane
# ---------------------------------------------------------------------------


def test_adversarial_job_demotes_only_its_own_lane(monkeypatch):
    """One tenant with a time-dip (non-monotone) workload shares the fleet
    with monotone tenants: the stacked partition must run with a MIXED
    per-lane mask (spied on the jit kernel), the adversarial job's bank
    must classify non-monotone, and every job — adversarial included —
    must still match its independent loop bit-for-bit."""
    rng = np.random.default_rng(300)
    p, q = 4, 3
    base, knee = _knee_params(rng, q, p)
    dip_scalar, dip_row = _dip_fns(p)

    def batch(X):
        T = _knee_time(base, knee, X)
        T[1] = dip_row(X[1])
        return T

    real = mbj._partition_units_jit
    masks = []

    def spy(*args, **kw):
        masks.append(np.array(args[8]))
        return real(*args, **kw)

    monkeypatch.setattr(mbj, "_partition_units_jit", spy)

    with jax.enable_x64():
        # independent references
        indep = []
        for j in range(q):
            fns = dip_scalar if j == 1 else _scalar_fns(base, knee, j)
            ex1 = SimulatedExecutor(time_fns=fns)
            sched = Scheduler(SpeedStore.empty(p, backend="jax"), backend="jax")
            res = sched.autotune(ex1, 90, 0.02, max_iter=6, min_units=1)
            indep.append(
                dict(res=res, cost=ex1.total_cost,
                     points=[m.as_points() for m in sched.store.models])
            )
        masks.clear()
        fleet = FleetScheduler(p, backend="jax")
        for j in range(q):
            fleet.admit(JobSpec(name=str(j), n=90, eps=0.02, min_units=1, max_iter=6))
        ex = BatchedSimulatedExecutor2D(
            time_fn_batch_2d=batch, p=p, q=q, job_names=[str(j) for j in range(q)]
        )
        results = fleet.run(ex)

    for j in range(q):
        _assert_job_parity(
            indep[j], results[str(j)], fleet.bench_cost(str(j)),
            [m.as_points() for m in fleet.models(str(j))],
        )
    # the adversarial job's host bank is non-monotone, neighbours' are not
    # (resolved via the bank: the cached flag is invalidated by every fold)
    assert fleet._jobs["1"].bank().is_monotone() is False
    assert fleet._jobs["0"].bank().is_monotone() is True
    assert fleet._jobs["2"].bank().is_monotone() is True
    # ... and at least one stacked call ran with a mixed per-lane mask
    stacked_masks = [m for m in masks if m.shape == (q,)]
    assert any(m[1] == False and m[0] and m[2] for m in stacked_masks)  # noqa: E712


# ---------------------------------------------------------------------------
# Per-job knobs: mixed n / eps / caps / completion
# ---------------------------------------------------------------------------


def test_mixed_caps_and_min_units_respected():
    rng = np.random.default_rng(400)
    p = 5
    base, knee = _knee_params(rng, 2, p)
    caps = [8, 40, 40, 40, 40]
    with jax.enable_x64():
        fleet = FleetScheduler(p, backend="jax")
        fleet.admit(JobSpec(name="capped", n=60, eps=0.05, caps=caps, min_units=1))
        fleet.admit(JobSpec(name="free", n=95, eps=0.05, min_units=2))
        ex = BatchedSimulatedExecutor2D(
            time_fn_batch_2d=_batch_fn(base, knee), p=p, q=2,
            job_names=["capped", "free"],
        )
        results = fleet.run(ex)
    d_c = results["capped"].allocations
    assert sum(d_c) == 60 and all(1 <= v <= c for v, c in zip(d_c, caps))
    d_f = results["free"].allocations
    assert sum(d_f) == 95 and all(v >= 2 for v in d_f)


def test_admit_validation_mirrors_autotune():
    fleet = FleetScheduler(4, backend="numpy")
    with pytest.raises(ValueError, match="n >= p"):
        fleet.admit(JobSpec(name="a", n=3))
    with pytest.raises(ValueError, match="eps"):
        fleet.admit(JobSpec(name="a", n=8, eps=0.0))
    with pytest.raises(ValueError, match="min_units"):
        fleet.admit(JobSpec(name="a", n=8, caps=[1, 8, 8, 8], min_units=2))
    with pytest.raises(ValueError, match="warm_start_d"):
        fleet.admit(JobSpec(name="a", n=8, warm_start_d=[1, 1, 1]))
    fleet.admit(JobSpec(name="a", n=8))
    with pytest.raises(ValueError, match="already admitted"):
        fleet.admit(JobSpec(name="a", n=12))
    with pytest.raises(ValueError, match="completion"):
        fleet.admit(JobSpec(name="b", n=8, completion="fast"))


# ---------------------------------------------------------------------------
# Profile registry: warm-start round-trip + corruption fallbacks
# ---------------------------------------------------------------------------

CLASSES = ["cpu", "cpu", "gpu", "gpu"]


def _class_fns(p=4):
    """Same-class processors share EXACT time fns, so class-keyed profile
    merging is lossless and the round-trip can be bit-identical."""
    per_class = {"cpu": (9e-4, 25.0), "gpu": (3e-4, 70.0)}
    a = np.asarray([[per_class[c][0] for c in CLASSES]])
    k = np.asarray([[per_class[c][1] for c in CLASSES]])
    return a, k


def test_registry_roundtrip_reproduces_donor_allocations(tmp_path):
    """Warm-starting from a saved registry reproduces the donor session's
    next-round allocations bit-identically."""
    a, k = _class_fns()
    p = 4
    ex = BatchedSimulatedExecutor2D(
        time_fn_batch_2d=_batch_fn(a, k), p=p, q=1, job_names=["donor"]
    )
    with jax.enable_x64():
        reg = ProfileRegistry()
        donor = FleetScheduler(
            p, backend="jax", registry=reg, device_classes=CLASSES
        )
        donor.admit(JobSpec(name="donor", n=80, eps=1e-9, min_units=1,
                            max_iter=4, workload="matmul"))
        donor.run(ex)
        # what the donor would do next: a repartition from its estimates
        donor_sched = Scheduler(
            SpeedStore.from_models(
                [PiecewiseLinearFPM.from_points(m.as_points())
                 for m in donor.models("donor")],
                backend="jax",
            ),
            backend="jax",
        )
        want = donor_sched.partition(80, min_units=1).allocations

        donor.save_profiles()
        path = tmp_path / "profiles.json"
        reg.save(str(path))

        reg2 = ProfileRegistry.load(str(path))
        fleet2 = FleetScheduler(
            p, backend="jax", registry=reg2, device_classes=CLASSES
        )
        fleet2.admit(JobSpec(name="fresh", n=80, eps=1e-9, min_units=1,
                             max_iter=1, workload="matmul"))
        ex2 = BatchedSimulatedExecutor2D(
            time_fn_batch_2d=_batch_fn(a, k), p=p, q=1, job_names=["fresh"]
        )
        fleet2.run(ex2)
    first_d = fleet2._jobs["fresh"].history[0][0]
    assert first_d == want  # NOT the even split: warm start engaged
    assert first_d != _even(80, p)


def test_registry_missing_workload_starts_cold():
    a, k = _class_fns()
    reg = ProfileRegistry()
    reg.record("cpu", "other-workload", [(10.0, 5.0)])
    with jax.enable_x64():
        fleet = FleetScheduler(
            4, backend="jax", registry=reg, device_classes=CLASSES
        )
        fleet.admit(JobSpec(name="j", n=80, eps=0.05, min_units=1, max_iter=2,
                            workload="matmul"))
        ex = BatchedSimulatedExecutor2D(
            time_fn_batch_2d=_batch_fn(a, k), p=4, q=1, job_names=["j"]
        )
        fleet.run(ex)
    assert fleet._jobs["j"].history[0][0] == _even(80, 4)


def test_registry_missing_file_warns_and_starts_cold(tmp_path):
    with pytest.warns(UserWarning, match="not found"):
        reg = ProfileRegistry.load(str(tmp_path / "nope.json"))
    assert len(reg) == 0


def test_registry_corrupt_json_warns_and_starts_cold(tmp_path):
    path = tmp_path / "broken.json"
    path.write_text("{ this is not json")
    with pytest.warns(UserWarning, match="unreadable"):
        reg = ProfileRegistry.load(str(path))
    assert len(reg) == 0
    path.write_text(json.dumps({"version": 1, "entries": "nope"}))
    with pytest.warns(UserWarning, match="malformed"):
        reg = ProfileRegistry.load(str(path))
    assert len(reg) == 0


def test_registry_malformed_entry_skipped_with_warning(tmp_path):
    path = tmp_path / "mixed.json"
    path.write_text(
        json.dumps(
            {
                "version": 1,
                "entries": [
                    {"device_class": "cpu", "workload": "w",
                     "points": [[10.0, 5.0], [20.0, 4.0]]},
                    {"device_class": "gpu", "workload": "w",
                     "points": [[-3.0, 5.0]]},  # non-positive x
                    {"device_class": "tpu", "workload": "w",
                     "points": [[30.0, "bad"]]},
                ],
            }
        )
    )
    with pytest.warns(UserWarning, match="malformed"):
        reg = ProfileRegistry.load(str(path))
    assert reg.get("cpu", "w") == [(10.0, 5.0), (20.0, 4.0)]
    assert ("gpu", "w") not in reg and ("tpu", "w") not in reg
    # warm_models: valid class warm, broken/absent classes cold
    models = reg.warm_models(["cpu", "gpu"], "w")
    assert models[0].num_points == 2 and models[1].num_points == 0


def test_registry_merge_keeps_freshest_on_duplicate_x():
    reg = ProfileRegistry()
    reg.record("cpu", "w", [(10.0, 5.0), (20.0, 4.0)])
    reg.record("cpu", "w", [(10.0, 6.0), (30.0, 3.0)])
    assert reg.get("cpu", "w") == [(10.0, 6.0), (20.0, 4.0), (30.0, 3.0)]


# ---------------------------------------------------------------------------
# Energy: power-capped repartition + registry energy entries
# ---------------------------------------------------------------------------


def _energy_fixtures(p, seed):
    """Heterogeneous speed + affine energy models (fast rows power-hungry)."""
    from repro.core.energy import energy_model

    rng = np.random.default_rng(seed)
    xs = [1.0, 10.0, 50.0, 200.0, 800.0]
    speed = [
        PiecewiseLinearFPM.from_points(
            [(x, float(1.0 + 2.0 * rng.random()) * (1.0 + 0.1 * (i % 3)))
             for x in xs]
        )
        for i in range(p)
    ]
    energy = [
        energy_model(
            [(x, 3.0 * (i + 1) + float(0.1 + rng.random()) * x) for x in xs]
        )
        for i in range(p)
    ]
    return speed, energy


def _fleet_round_energy(fleet, name, d):
    job = fleet._jobs[name]
    e = job.ebank().time(np.asarray(d, dtype=np.float64))
    return float(e.sum())


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_power_cap_binds_and_fits_budget(backend):
    """A binding power_cap yields per-round allocations whose predicted
    fleet energy fits the budget; an uncapped fleet with identical models
    overspends it (the cap actually binds)."""
    from repro.core.partition import _partition_units_bank

    p = 5

    def build(cap):
        fl = FleetScheduler(p, backend=backend, power_cap=cap)
        for j, n in enumerate((300, 500)):
            sm, em = _energy_fixtures(p, seed=10 + j)
            fl.admit(JobSpec(str(j), n), models=sm, energy_models=em)
        return fl

    with jax.enable_x64():
        free = build(None)
        ds0 = free.rebalance()
        e_free = sum(_fleet_round_energy(free, nm, d) for nm, d in ds0.items())
        # the energy-optimal floor: per-job min-max-energy partitions
        e_floor = 0.0
        for nm, job in free._jobs.items():
            de, _ = _partition_units_bank(
                job.ebank(), job.spec.n, [int(c) for c in job.icaps],
                min_units=0,
            )
            e_floor += _fleet_round_energy(free, nm, de)
        assert e_floor < e_free  # non-degenerate: the cap can bind
        cap = 0.5 * (e_floor + e_free)

        capped = build(cap)
        ds1 = capped.rebalance()
        e_capped = sum(
            _fleet_round_energy(capped, nm, d) for nm, d in ds1.items()
        )
    assert e_capped <= cap + 1e-9
    assert e_free > cap  # uncapped would overspend
    for nm, d in ds1.items():
        assert sum(d) == capped._jobs[nm].spec.n


def test_power_cap_none_is_bit_identical():
    """power_cap=None must not perturb a single allocation (do-no-harm)."""
    p = 4
    with jax.enable_x64():
        a = FleetScheduler(p, backend="jax")
        b = FleetScheduler(p, backend="jax", power_cap=None)
        sm, em = _energy_fixtures(p, seed=3)
        for fl in (a, b):
            fl.admit(JobSpec("t", 200), models=sm, energy_models=em)
        assert a.rebalance() == b.rebalance()


def test_power_cap_unpriced_jobs_run_time_optimal():
    """Jobs without energy models keep their time-optimal allocations and
    are excluded from the budget."""
    p = 4
    sm, em = _energy_fixtures(p, seed=7)
    with jax.enable_x64():
        free = FleetScheduler(p, backend="jax")
        free.admit(JobSpec("u", 240), models=sm)
        want = free.rebalance()["u"]
        capped = FleetScheduler(p, backend="jax", power_cap=1e-6)
        capped.admit(JobSpec("u", 240), models=sm)
        assert capped.rebalance()["u"] == want


def test_power_cap_infeasible_degrades_to_energy_optimal():
    from repro.core.partition import _partition_units_bank

    p = 4
    sm, em = _energy_fixtures(p, seed=9)
    with jax.enable_x64():
        fl = FleetScheduler(p, backend="numpy", power_cap=1e-9)
        fl.admit(JobSpec("t", 200), models=sm, energy_models=em)
        d = fl.rebalance()["t"]
        job = fl._jobs["t"]
        de, _ = _partition_units_bank(
            job.ebank(), 200, [int(c) for c in job.icaps], min_units=0
        )
    assert d == [int(v) for v in de]


def test_registry_energy_entries_roundtrip(tmp_path):
    """Energy profiles persist beside speed ones and warm-start the next
    session's admits; older-format states load clean without them."""
    p = 4
    classes = ["cpu", "cpu", "gpu", "gpu"]
    # same-class rows share energy models so class-keyed merging is lossless
    from repro.core.energy import energy_model

    xs = [1.0, 10.0, 100.0]
    per_class = {"cpu": (5.0, 0.9), "gpu": (20.0, 0.3)}
    em = [
        energy_model([(x, per_class[c][0] + per_class[c][1] * x) for x in xs])
        for c in classes
    ]
    sm, _ = _energy_fixtures(p, seed=1)
    reg = ProfileRegistry()
    fl = FleetScheduler(p, backend="numpy", registry=reg, device_classes=classes)
    fl.admit(JobSpec("d", 100, workload="decode"), models=sm, energy_models=em)
    fl.rebalance()
    fl.retire("d")
    path = tmp_path / "profiles.json"
    reg.save(str(path))
    reg2 = ProfileRegistry.load(str(path))
    warm = reg2.warm_energy_models(classes, "decode")
    assert warm is not None and len(warm) == p
    assert warm[0].as_points() == em[0].as_points()
    # a new admit picks the energy profile up from the registry
    fl2 = FleetScheduler(p, backend="numpy", registry=reg2, device_classes=classes)
    fl2.admit(JobSpec("d2", 100, workload="decode"), models=sm)
    assert fl2._jobs["d2"].energy_models is not None
    # all-or-nothing: a class without an energy entry means no warm bank
    assert reg2.warm_energy_models(["cpu", "tpu"], "decode") is None
    # pre-energy states (no energy_entries field) load clean
    state = reg2.state_dict()
    state.pop("energy_entries")
    assert ProfileRegistry.from_state(state).warm_energy_models(
        classes, "decode"
    ) is None


# ---------------------------------------------------------------------------
# Lane buckets: padded stacks, bit parity, zero recompiles within a bucket
# ---------------------------------------------------------------------------


def test_lane_buckets_bit_parity_and_zero_recompiles():
    """Bucketed fleets serve bit-identical allocations to unbucketed ones,
    and an admit WITHIN a power-of-two bucket reuses both compiled device
    programs (zero recompiles — the satellite's contract)."""
    p = 5

    def mk(buckets):
        fl = FleetScheduler(
            p, backend="jax", reserve_knots=16, lane_buckets=buckets
        )
        for j in range(3):
            sm, _ = _energy_fixtures(p, seed=30 + j)
            fl.admit(JobSpec(f"j{j}", 150 + 40 * j), models=sm)
        return fl

    with jax.enable_x64():
        plain, bucketed = mk(False), mk(True)
        assert plain.rebalance() == bucketed.rebalance()
        # pad 3 -> 4 lanes: the stacked carry is wider than the job list
        assert int(bucketed._stacked.counts.shape[0]) == 4
        assert len(bucketed._stack_names) == 3

        # warm both programs at the padded shape, then admit within bucket
        bucketed.observe({"j0": [0.1 * (i + 1) for i in range(p)]})
        bucketed.rebalance()
        c0 = mbj._partition_units_jit._cache_size()
        f0 = mbj._fold_in_jit._cache_size()
        sm, _ = _energy_fixtures(p, seed=33)
        bucketed.admit(JobSpec("j3", 400), models=sm)
        ds = bucketed.rebalance()
        bucketed.observe({"j3": [0.1 * (i + 1) for i in range(p)]})
        assert mbj._partition_units_jit._cache_size() == c0
        assert mbj._fold_in_jit._cache_size() == f0
        assert sum(ds["j3"]) == 400

        # parity holds after the admit too (same folds replayed)
        plain.observe({"j0": [0.1 * (i + 1) for i in range(p)]})
        plain.admit(JobSpec("j3", 400), models=sm)
        assert plain.rebalance() == ds


def test_lane_buckets_full_autotune_parity():
    """The measured lock-step loop (step/run) is bit-identical under
    bucketing — dead lanes must be exact no-ops through partition AND
    fold."""
    rng = np.random.default_rng(500)
    p, q = 4, 3  # q=3 pads to 4: one dead lane in every program
    base, knee = _knee_params(rng, q, p)

    def run(buckets):
        fleet = FleetScheduler(p, backend="jax", lane_buckets=buckets)
        for j in range(q):
            fleet.admit(
                JobSpec(name=str(j), n=50 + 30 * j, eps=0.05, min_units=1,
                        max_iter=6)
            )
        ex = BatchedSimulatedExecutor2D(
            time_fn_batch_2d=_batch_fn(base, knee), p=p, q=q,
            job_names=[str(j) for j in range(q)],
        )
        results = fleet.run(ex)
        return fleet, results

    with jax.enable_x64():
        fa, ra = run(False)
        fb, rb = run(True)
    for j in range(q):
        name = str(j)
        assert ra[name].allocations == rb[name].allocations
        assert ra[name].diagnostics["history"] == rb[name].diagnostics["history"]
        assert [m.as_points() for m in fa.models(name)] == [
            m.as_points() for m in fb.models(name)
        ]


# ---------------------------------------------------------------------------
# Serving fleet mode
# ---------------------------------------------------------------------------


def test_replica_dispatcher_fleet_mode():
    from repro.runtime.serve_loop import ReplicaDispatcher

    base = [4e-4, 2e-4, 8e-4, 3e-4]

    def replica_run(i, x):
        t = x * base[i]
        if x > 30:
            t += (x - 30) * base[i] * 3.0
        return t

    disp = ReplicaDispatcher(replica_run, 4, eps=0.15)
    with jax.enable_x64():
        results = disp.balance_fleet(
            {"chat": 48, "embed": 96}, backend="jax", min_units=1
        )
        assert set(results) == {"chat", "embed"}
        assert sum(results["chat"].allocations) == 48
        assert sum(results["embed"].allocations) == 96
        assert disp.fleet is not None and disp.fleet.jobs == ["chat", "embed"]
        # the warm session keeps serving: resize a tenant and continue
        # (inside the same x64 scope — the device carry's dtype is fixed)
        disp.fleet.resize("chat", n=64)
        more = disp.fleet.run(disp)
    assert sum(more["chat"].allocations) == 64
