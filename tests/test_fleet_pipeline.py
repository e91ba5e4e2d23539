"""Round-semantics conformance suite for the pipelined fleet driver.

The contract this file locks (see "Round lifecycle: sync vs pipelined" in
``fleet/scheduler.py``):

* ``pipeline=False`` (the default) stays bit-identical to the pre-pipeline
  sync rounds — re-proven here against q independent autotune sessions on
  top of ``test_fleet.py``'s existing lanes.
* ``pipeline=True, pipeline_depth=0`` reads only the newest carry and is
  bit-identical to sync (the pre-dispatch machinery must be a pure no-op
  semantically).
* ``pipeline=True, pipeline_depth=1`` may partition against estimates one
  fold generation old — never more — and converges to the SAME fixed point
  as sync within <= 2 extra rounds on every fuzz case.
* Every interleaving of fold-vs-partition completion order (forced through
  the deterministic ``fold_ready_hook`` seam) reaches that same fixed
  point; the all-fold-first schedule is bit-identical to sync.
* Mid-flight ``admit``/``retire``/``resize`` and mid-round ``state_dict``
  round-trips preserve those guarantees (the pipeline drains or discards
  its pre-dispatched work, never serves it across a membership change).

Fuzz lanes follow the repo convention: numpy-rng lanes >= 200 cases under
the ``slow`` marker with small tier-1 smokes.
"""

import itertools
import json

import numpy as np
import pytest

import jax

from repro.core import (
    BatchedSimulatedExecutor2D,
    DelayedBatchedExecutor,
    SpeedStore,
)
from repro.fleet import FleetScheduler, JobSpec

from test_fleet import (
    BIT_EXACT,
    _batch_fn,
    _check_fleet_parity,
    _energy_fixtures,
    _knee_params,
    _random_fleet_case,
)


# ---------------------------------------------------------------------------
# Case builders / runners
# ---------------------------------------------------------------------------


def _converging_case(rng):
    """Like ``_random_fleet_case`` but guaranteed head-room to converge:
    moderate eps, generous max_iter, no caps — the bounded-staleness lane
    asserts BOTH modes reach the eps test, so probe-exhaustion cut-offs
    (which freeze a lagged pipeline allocation by design) are excluded."""
    p = int(rng.integers(2, 7))
    q = int(rng.integers(1, 5))
    base, knee = _knee_params(rng, q, p)
    jobs = [
        dict(
            n=int(rng.integers(max(2 * p, 8), 60 * p)),
            eps=float(rng.uniform(0.06, 0.25)),
            caps=None,
            min_units=1,
            max_iter=24,
        )
        for _ in range(q)
    ]
    return dict(p=p, q=q, base=base, knee=knee, jobs=jobs)


def _mk_fleet(case, backend, **kw):
    fleet = FleetScheduler(case["p"], backend=backend, **kw)
    for j, spec in enumerate(case["jobs"]):
        fleet.admit(
            JobSpec(
                name=str(j),
                n=spec["n"],
                eps=spec["eps"],
                caps=spec["caps"],
                min_units=spec["min_units"],
                max_iter=spec["max_iter"],
            )
        )
    return fleet


def _mk_ex(case):
    return BatchedSimulatedExecutor2D(
        time_fn_batch_2d=_batch_fn(case["base"], case["knee"]),
        p=case["p"],
        q=case["q"],
        job_names=[str(j) for j in range(case["q"])],
    )


def _run_case(case, backend, **kw):
    fleet = _mk_fleet(case, backend, **kw)
    return fleet, fleet.run(_mk_ex(case))


def _assert_fleet_equal(fa, ra, fb, rb, q):
    """Full bit-identity between two fleet sessions over the same case."""
    for j in range(q):
        name = str(j)
        a, b = ra[name], rb[name]
        assert a.allocations == b.allocations
        assert a.times == b.times
        assert a.iterations == b.iterations
        assert a.converged == b.converged
        assert a.imbalance == b.imbalance
        assert a.diagnostics["history"] == b.diagnostics["history"]
        assert fa.bench_cost(name) == fb.bench_cost(name)
        assert [m.as_points() for m in fa.models(name)] == [
            m.as_points() for m in fb.models(name)
        ]


def _check_depth0_identity(case, backend):
    fs, rs = _run_case(case, backend)
    fp, rp = _run_case(case, backend, pipeline=True, pipeline_depth=0)
    _assert_fleet_equal(fs, rs, fp, rp, case["q"])
    assert fp.stale_reads == 0  # depth 0 never reads the stale generation


def _check_bounded_staleness(case, backend):
    """The depth-1 conformance bound: same fixed point as sync within <= 2
    extra rounds.  On a deterministic replay the seen-set validation makes
    every speculation miss, so the trajectory is bit-identical (0 extra) —
    asserted in full; the speculative machinery must actually have run."""
    fs, rs = _run_case(case, backend)
    fp, rp = _run_case(case, backend, pipeline=True, pipeline_depth=1)
    for j in range(case["q"]):
        name = str(j)
        assert rp[name].allocations == rs[name].allocations
        assert rp[name].converged == rs[name].converged
        assert sum(rp[name].allocations) == case["jobs"][j]["n"]
    _assert_fleet_equal(fs, rs, fp, rp, case["q"])
    assert fp.rounds <= fs.rounds + 2
    if fp.rounds >= 4:  # long enough for the stale generation to exist
        assert fp.stale_reads + fp.speculative_misses > 0


# ---------------------------------------------------------------------------
# Construction contract
# ---------------------------------------------------------------------------


def test_pipeline_validation():
    with pytest.raises(ValueError, match="banked backend"):
        FleetScheduler(4, backend="scalar", pipeline=True)
    with pytest.raises(ValueError, match="pipeline_depth"):
        FleetScheduler(4, backend="numpy", pipeline=True, pipeline_depth=2)
    for backend in ("numpy", "jax"):
        fl = FleetScheduler(4, backend=backend, pipeline=True)
        assert fl.pipeline and fl.pipeline_depth == 1


# ---------------------------------------------------------------------------
# depth 0 == sync, bit for bit
# ---------------------------------------------------------------------------


def test_depth0_bit_identical_to_sync_jax_smoke():
    rng = np.random.default_rng(900)
    with jax.enable_x64():
        for _ in range(4):
            _check_depth0_identity(_random_fleet_case(rng), "jax")


def test_depth0_bit_identical_to_sync_numpy_smoke():
    rng = np.random.default_rng(901)
    for _ in range(5):
        _check_depth0_identity(_random_fleet_case(rng), "numpy")


@pytest.mark.slow
def test_depth0_bit_identity_fuzz_numpy_lane():
    rng = np.random.default_rng(902)
    for _ in range(200):
        _check_depth0_identity(_random_fleet_case(rng), "numpy")


@pytest.mark.slow
def test_depth0_bit_identity_fuzz_jax_lane():
    rng = np.random.default_rng(903)
    with jax.enable_x64():
        for _ in range(200):
            _check_depth0_identity(_random_fleet_case(rng), "jax")


@pytest.mark.slow
def test_sync_default_bit_identity_fuzz_lane():
    """The default-mode guarantee, re-proven from this suite's seeds: a
    post-refactor sync fleet still matches q independent autotune loops."""
    rng = np.random.default_rng(904)
    for _ in range(200):
        _check_fleet_parity(_random_fleet_case(rng), "numpy")


# ---------------------------------------------------------------------------
# depth 1: bounded staleness, same fixed point, <= 2 extra rounds
# ---------------------------------------------------------------------------


def test_bounded_staleness_jax_smoke():
    rng = np.random.default_rng(910)
    with jax.enable_x64():
        for _ in range(4):
            _check_bounded_staleness(_converging_case(rng), "jax")


def test_bounded_staleness_numpy_smoke():
    rng = np.random.default_rng(911)
    for _ in range(5):
        _check_bounded_staleness(_converging_case(rng), "numpy")


@pytest.mark.slow
def test_bounded_staleness_fuzz_numpy_lane():
    rng = np.random.default_rng(912)
    for _ in range(200):
        _check_bounded_staleness(_converging_case(rng), "numpy")


@pytest.mark.slow
def test_bounded_staleness_fuzz_jax_lane():
    rng = np.random.default_rng(913)
    with jax.enable_x64():
        for _ in range(200):
            _check_bounded_staleness(_converging_case(rng), "jax")


def test_staleness_bound_never_exceeds_one_generation():
    """The carry a pipelined repartition may read is never more than ONE
    fold generation behind the newest — checked after every round via the
    generation tags the carries carry — and the speculative machinery
    (stale dispatch + validation) actually ran."""
    rng = np.random.default_rng(914)
    case = _converging_case(rng)
    fleet = _mk_fleet(case, "jax", pipeline=True, pipeline_depth=1)
    ex = _mk_ex(case)
    with jax.enable_x64():
        for _ in range(10):
            if not fleet.active_jobs:
                break
            fleet.step(ex)
            if fleet._stacked_stale is not None:
                gap = fleet._stacked.generation - fleet._stacked_stale.generation
                assert 0 <= gap <= 1
    assert fleet.stale_reads + fleet.speculative_misses > 0
    assert fleet.predispatches > 0  # overlapped partitions were dispatched


def test_speedstore_fold_generation_counter():
    store = SpeedStore.empty(3, backend="numpy")
    assert store.fold_generation == 0
    store.fold_in([4, 5, 6], [0.1, 0.2, 0.3])
    assert store.fold_generation == 1
    store.fold_in([8, 9, 10], [0.2, 0.3, 0.4])
    assert store.fold_generation == 2


# ---------------------------------------------------------------------------
# Genuine stale acceptance: the rounds where speculation actually wins
# ---------------------------------------------------------------------------


def test_serving_rebalance_cycle_accepts_stale_read():
    """The steady-state serving epoch (observe -> rebalance, estimates
    preloaded, nothing measured through the seen set) is where depth-1
    speculation pays: the rebalance after a fold consumes the overlapped
    stale partition instead of waiting on the in-flight fold, lagging it
    by exactly one generation; a drained fresh rebalance then matches the
    sync fleet's post-fold answer bit-for-bit."""
    p = 5

    def build(pipeline):
        kw = dict(pipeline=True, pipeline_depth=1) if pipeline else {}
        fl = FleetScheduler(p, backend="jax", **kw)
        for j, n in enumerate((300, 500)):
            sm, _ = _energy_fixtures(p, seed=20 + j)
            fl.admit(JobSpec(str(j), n), models=sm)
        return fl

    epoch = {"0": [0.2 * (i + 1) for i in range(p)]}
    with jax.enable_x64():
        sync, pipe = build(False), build(True)
        assert sync.rebalance() == pipe.rebalance()  # no stale generation yet
        for fl in (sync, pipe):
            fl.observe(epoch)
        ds_sync = sync.rebalance()
        ds_pipe = pipe.rebalance()
        assert pipe.stale_reads == 1  # the overlapped partition was consumed
        for nm, d in ds_pipe.items():
            assert sum(d) == pipe._jobs[nm].spec.n
        # the stale read lags the fold by one generation; draining and
        # re-reading fresh reconverges onto the sync answer exactly
        pipe.drain()
        assert pipe.rebalance() == ds_sync


def test_resize_after_convergence_accepts_stale_and_reconverges():
    """A fleet-wide resize clears every seen set, so the next round's
    speculative partition is consumable (novel n, novel distributions):
    the re-run converges from one-generation-old estimates within eps."""
    rng = np.random.default_rng(915)
    p, q = 4, 2
    base, knee = _knee_params(rng, q, p)
    ex = BatchedSimulatedExecutor2D(
        time_fn_batch_2d=_batch_fn(base, knee),
        p=p,
        q=q,
        job_names=[str(j) for j in range(q)],
    )
    with jax.enable_x64():
        fleet = FleetScheduler(p, backend="jax", pipeline=True, pipeline_depth=1)
        for j in range(q):
            fleet.admit(
                JobSpec(name=str(j), n=60 + 40 * j, eps=0.15, min_units=1,
                        max_iter=20)
            )
        fleet.run(ex)
        fleet.resize("0", n=77)
        fleet.resize("1", n=131)
        pre = fleet.stale_reads
        res = fleet.run(ex)
    assert fleet.stale_reads > pre  # the resized round speculated and won
    assert sum(res["0"].allocations) == 77
    assert sum(res["1"].allocations) == 131
    assert res["0"].converged and res["1"].converged


# ---------------------------------------------------------------------------
# Deterministic interleaving enumeration (the fake-async seam)
# ---------------------------------------------------------------------------


def test_every_fold_vs_partition_interleaving_reaches_sync_fixed_point():
    """``fold_ready_hook`` forces the completion order per round: True
    means "the fold finished before the partition dispatched" (fresh read),
    False leaves the pipeline free to speculate on the stale generation.
    Every schedule in {fold-first, partition-first}^R must produce sync's
    results bit-for-bit within <= 2 extra rounds — the seen-set validation
    makes the completion order unobservable in the allocations."""
    rng = np.random.default_rng(920)
    case = _converging_case(rng)
    R = 4
    with jax.enable_x64():
        fs, rs = _run_case(case, "jax")
        for schedule in itertools.product([False, True], repeat=R):
            fleet = _mk_fleet(case, "jax", pipeline=True, pipeline_depth=1)
            fleet.fold_ready_hook = lambda s=schedule: s[min(fleet.rounds, R - 1)]
            rp = fleet.run(_mk_ex(case))
            _assert_fleet_equal(fs, rs, fleet, rp, case["q"])
            assert fleet.rounds <= fs.rounds + 2, schedule
            if all(schedule):
                # every round read fresh -> no speculation at all
                assert fleet.stale_reads == 0 and fleet.speculative_misses == 0


# ---------------------------------------------------------------------------
# Mid-flight admit / retire / resize under the pipeline
# ---------------------------------------------------------------------------


def _membership_script(fleet, ex, specs):
    """Shared mid-flight script: staggered admits, a retire, a resize."""
    fleet.admit(specs[0])
    fleet.step(ex)
    fleet.step(ex)
    fleet.admit(specs[1])  # restack: the pipeline must drain/discard
    fleet.step(ex)
    fleet.admit(specs[2])
    fleet.step(ex)
    retired = fleet.retire("1")
    fleet.resize("0", n=specs[0].n + 17)
    results = fleet.run(ex)
    return retired, results


def test_pipeline_depth0_membership_changes_bit_identical_to_sync():
    rng = np.random.default_rng(930)
    p, q = 4, 3
    base, knee = _knee_params(rng, q, p)
    specs = [
        JobSpec(name=str(j), n=40 + 30 * j, eps=0.05, min_units=1, max_iter=8)
        for j in range(q)
    ]

    def mk_ex():
        return BatchedSimulatedExecutor2D(
            time_fn_batch_2d=_batch_fn(base, knee),
            p=p,
            q=q,
            job_names=[str(j) for j in range(q)],
        )

    with jax.enable_x64():
        sync = FleetScheduler(p, backend="jax")
        ret_s, res_s = _membership_script(sync, mk_ex(), specs)
        pipe = FleetScheduler(p, backend="jax", pipeline=True, pipeline_depth=0)
        ret_p, res_p = _membership_script(pipe, mk_ex(), specs)
    assert ret_p.allocations == ret_s.allocations
    assert ret_p.diagnostics["history"] == ret_s.diagnostics["history"]
    for name in ("0", "2"):
        assert res_p[name].allocations == res_s[name].allocations
        assert res_p[name].times == res_s[name].times
        assert (
            res_p[name].diagnostics["history"]
            == res_s[name].diagnostics["history"]
        )
        assert pipe.bench_cost(name) == sync.bench_cost(name)


def test_pipeline_depth1_membership_changes_prefix_parity():
    """Depth 1 with mid-flight membership churn: the deterministic replay
    stays bit-identical to sync (every speculation misses its seen-set
    validation), the retired job's history is a bounded prefix of the rounds
    it ran, survivors reach correct sums, and the pre-dispatched partition
    is never served across a restack (its fingerprint covers the
    membership)."""
    rng = np.random.default_rng(931)
    p, q = 4, 3
    base, knee = _knee_params(rng, q, p)
    specs = [
        JobSpec(name=str(j), n=40 + 30 * j, eps=0.1, min_units=1, max_iter=20)
        for j in range(q)
    ]

    def mk_ex():
        return BatchedSimulatedExecutor2D(
            time_fn_batch_2d=_batch_fn(base, knee),
            p=p,
            q=q,
            job_names=[str(j) for j in range(q)],
        )

    with jax.enable_x64():
        sync = FleetScheduler(p, backend="jax")
        ret_s, res_s = _membership_script(sync, mk_ex(), specs)
        fleet = FleetScheduler(p, backend="jax", pipeline=True, pipeline_depth=1)
        retired, results = _membership_script(fleet, mk_ex(), specs)
    assert 0 < len(retired.diagnostics["history"]) <= 4
    assert retired.diagnostics["history"] == ret_s.diagnostics["history"]
    assert sum(results["0"].allocations) == specs[0].n + 17
    assert sum(results["2"].allocations) == specs[2].n
    for name in ("0", "2"):
        assert results[name].allocations == res_s[name].allocations
        assert results[name].converged == res_s[name].converged
        assert (
            results[name].diagnostics["history"]
            == res_s[name].diagnostics["history"]
        )
    # deterministic replay: every speculation misses, none consumed
    assert fleet.stale_reads == 0
    assert fleet.speculative_misses > 0
    assert fleet.predispatches > 0


# ---------------------------------------------------------------------------
# state_dict round-trip while a round is in flight (satellite 2)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "backend,pipeline", [("jax", False), ("jax", True), ("numpy", True)]
)
def test_state_dict_roundtrip_mid_flight(backend, pipeline):
    """Checkpointing a fleet with work in flight (pending fold carry, a
    pre-dispatched partition) must drain the pipeline and serialize a state
    whose restore continues bit-identically to the donor."""
    rng = np.random.default_rng(940)
    case = _converging_case(rng)
    kw = dict(pipeline=True, pipeline_depth=1) if pipeline else {}
    with jax.enable_x64():
        donor = _mk_fleet(case, backend, **kw)
        ex = _mk_ex(case)
        for _ in range(3):
            donor.step(ex)
        if pipeline and backend == "jax":
            assert donor._predispatched is not None  # genuinely mid-pipeline
        state = json.loads(json.dumps(donor.state_dict()))  # JSON-safe
        assert state["config"]["pipeline"] == bool(pipeline)
        restored = FleetScheduler.from_state(state)
        res_a = donor.run(ex)
        res_b = restored.run(_mk_ex(case))
    for j in range(case["q"]):
        name = str(j)
        assert res_a[name].allocations == res_b[name].allocations
        assert (
            res_a[name].diagnostics["history"]
            == res_b[name].diagnostics["history"]
        )
        assert res_a[name].converged == res_b[name].converged


def test_state_dict_drains_pipeline():
    rng = np.random.default_rng(941)
    case = _converging_case(rng)
    with jax.enable_x64():
        fleet = _mk_fleet(case, "jax", pipeline=True, pipeline_depth=1)
        ex = _mk_ex(case)
        fleet.step(ex)
        fleet.step(ex)
        fleet.state_dict()
    assert fleet._predispatched is None
    assert fleet._stacked_stale is None


# ---------------------------------------------------------------------------
# quantize= x lane_buckets=True composed (satellite 3)
# ---------------------------------------------------------------------------


def test_quantize_lane_buckets_composed_parity_and_dummy_lane_noop():
    """PR 7's quantized folds and PR 8's padded lane buckets compose: the
    bucketed fleet is bit-identical to the unbucketed one on the quantized
    knot grid, and the masked dummy lane's carry rows (the single-knot
    padding sentinel) stay EXACTLY untouched through every quantized fold —
    a fold that perturbed them would shift the shared knot grid and break
    the bucket's zero-recompile guarantee."""
    rng = np.random.default_rng(950)
    p, q = 4, 3  # q=3 pads to 4: one dummy lane in every program

    base, knee = _knee_params(rng, q, p)

    def run(buckets):
        fleet = FleetScheduler(
            p, backend="jax", quantize=0.05, lane_buckets=buckets
        )
        for j in range(q):
            fleet.admit(
                JobSpec(
                    name=str(j), n=50 + 30 * j, eps=0.05, min_units=1, max_iter=6
                )
            )
        snap = None
        if buckets:
            stacked = fleet._ensure_stack()
            snap = (
                np.asarray(stacked.counts)[q:].copy(),
                np.asarray(stacked.xs)[q:].copy(),
                np.asarray(stacked.ss)[q:].copy(),
            )
        ex = BatchedSimulatedExecutor2D(
            time_fn_batch_2d=_batch_fn(base, knee),
            p=p,
            q=q,
            job_names=[str(j) for j in range(q)],
        )
        results = fleet.run(ex)
        return fleet, results, snap

    with jax.enable_x64():
        fa, ra, _ = run(False)
        fb, rb, snap = run(True)
        # padded stack: 4 lanes for 3 jobs, the 4th masked out
        assert int(fb._stacked.counts.shape[0]) == 4
        dummy_counts = np.asarray(fb._stacked.counts)[q:]
        dummy_xs = np.asarray(fb._stacked.xs)[q:]
        dummy_ss = np.asarray(fb._stacked.ss)[q:]
    # every quantized fold left the dummy rows bit-identical to the
    # padding sentinel captured before any measurement was folded in: same
    # knot counts, same valid knots.  (Folds may GROW the shared padded
    # knot-capacity axis — the pad replicates the last knot — so only the
    # valid prefix is comparable across the run.)
    assert np.array_equal(dummy_counts, snap[0])
    kv = int(snap[0].max())  # sentinel width: one knot per processor
    assert np.array_equal(dummy_xs[..., :kv], snap[1][..., :kv])
    assert np.array_equal(dummy_ss[..., :kv], snap[2][..., :kv])
    for j in range(q):
        name = str(j)
        assert ra[name].allocations == rb[name].allocations
        assert ra[name].diagnostics["history"] == rb[name].diagnostics["history"]
        assert [m.as_points() for m in fa.models(name)] == [
            m.as_points() for m in fb.models(name)
        ]


def test_quantize_lane_buckets_pipeline_composed():
    """All three compose: quantized folds + padded buckets + depth-0
    pipeline stay bit-identical to the plain quantized sync fleet."""
    rng = np.random.default_rng(951)
    p, q = 4, 3
    base, knee = _knee_params(rng, q, p)
    case = dict(
        p=p,
        q=q,
        base=base,
        knee=knee,
        jobs=[
            dict(n=50 + 30 * j, eps=0.05, caps=None, min_units=1, max_iter=6)
            for j in range(q)
        ],
    )
    with jax.enable_x64():
        fs, rs = _run_case(case, "jax", quantize=0.05)
        fp, rp = _run_case(
            case,
            "jax",
            quantize=0.05,
            lane_buckets=True,
            pipeline=True,
            pipeline_depth=0,
        )
    for j in range(q):
        name = str(j)
        assert rs[name].allocations == rp[name].allocations
        assert rs[name].diagnostics["history"] == rp[name].diagnostics["history"]


# ---------------------------------------------------------------------------
# Power cap + hierarchy routes under the pipeline
# ---------------------------------------------------------------------------


def test_pipeline_power_cap_reads_consistent_generation():
    """A power cap forces every priced repartition onto the newest carry
    (``_apply_power_cap`` prices time and energy against ONE generation):
    the capped pipeline fleet matches the capped sync fleet bit-for-bit
    and never counts a stale read."""
    p = 5

    def build(pipeline):
        kw = dict(pipeline=True, pipeline_depth=1) if pipeline else {}
        fl = FleetScheduler(p, backend="jax", power_cap=50.0, **kw)
        for j, n in enumerate((300, 500)):
            sm, em = _energy_fixtures(p, seed=10 + j)
            fl.admit(JobSpec(str(j), n), models=sm, energy_models=em)
        return fl

    with jax.enable_x64():
        sync, pipe = build(False), build(True)
        assert sync.rebalance() == pipe.rebalance()
        for fl in (sync, pipe):
            fl.observe({"0": [0.1 * (i + 1) for i in range(p)]})
        assert sync.rebalance() == pipe.rebalance()
    assert pipe.stale_reads == 0


def test_pipeline_hier_route_depth0_matches_sync():
    class _FleetExec:
        def __init__(self, p, seed=3):
            r = np.random.default_rng(seed)
            self.base = r.uniform(5.0, 50.0, size=p)
            self.bend = r.uniform(50, 400, size=p)
            self.num_procs = p

        def run_jobs(self, names, D):
            D = np.asarray(D, dtype=np.float64)
            s = self.base * (1.0 + 0.3 * np.minimum(D, self.bend) / self.bend)
            return np.where(D > 0, D / s, 0.0)

    p = 8
    groups = [i % 2 for i in range(p)]

    def run(**kw):
        fs = FleetScheduler(p, backend="jax", groups=groups, **kw)
        fs.admit(JobSpec(name="a", n=500, eps=0.05, max_iter=8))
        fs.admit(JobSpec(name="b", n=700, eps=0.05, max_iter=8))
        res = fs.run(_FleetExec(p), max_rounds=10)
        return fs, {k: (v.allocations, v.diagnostics["history"]) for k, v in res.items()}

    with jax.enable_x64():
        _, sync = run()
        _, d0 = run(pipeline=True, pipeline_depth=0)
        _, d1 = run(pipeline=True, pipeline_depth=1)
    if BIT_EXACT:
        assert sync == d0
        assert sync == d1  # deterministic replay: every speculation misses
    for k in sync:
        assert sum(d1[k][0]) == sum(sync[k][0])


# ---------------------------------------------------------------------------
# DelayedBatchedExecutor (satellite 1): the reproducible async double
# ---------------------------------------------------------------------------


def test_delayed_executor_preserves_times_and_fleet_parity():
    rng = np.random.default_rng(960)
    case = _converging_case(rng)
    lat = {str(j): 0.5 * j for j in range(case["q"])}
    with jax.enable_x64():
        fs, rs = _run_case(case, "jax", pipeline=True, pipeline_depth=1)
        fleet = _mk_fleet(case, "jax", pipeline=True, pipeline_depth=1)
        wrapped = DelayedBatchedExecutor(inner=_mk_ex(case), lane_latency=lat, seed=7)
        rw = fleet.run(wrapped)
    for j in range(case["q"]):
        name = str(j)
        assert rw[name].allocations == rs[name].allocations
        assert rw[name].times == rs[name].times
        assert (
            rw[name].diagnostics["history"] == rs[name].diagnostics["history"]
        )
    assert len(wrapped.completions) > 0
    assert wrapped.clock > 0.0


def test_delayed_executor_seeded_reproducibility_and_straggler_order():
    rng = np.random.default_rng(961)
    p, q = 4, 3
    base, knee = _knee_params(rng, q, p)

    def mk(seed, lat):
        return DelayedBatchedExecutor(
            inner=BatchedSimulatedExecutor2D(
                time_fn_batch_2d=_batch_fn(base, knee),
                p=p,
                q=q,
                job_names=[str(j) for j in range(q)],
            ),
            lane_latency=lat,
            seed=seed,
        )

    D = [[10, 12, 8, 5]] * q
    names = [str(j) for j in range(q)]

    # same seed -> identical completion logs, different latency -> the
    # straggler ("1") completes last while times stay bit-equal to bare
    straggler = {"0": 0.0, "1": 10.0, "2": 0.0}
    a, b = mk(0, straggler), mk(0, straggler)
    Ta = np.asarray(a.run_jobs(names, D))
    Tb = np.asarray(b.run_jobs(names, D))
    bare = BatchedSimulatedExecutor2D(
        time_fn_batch_2d=_batch_fn(base, knee),
        p=p,
        q=q,
        job_names=names,
    )
    assert np.array_equal(Ta, np.asarray(bare.run_jobs(names, D)))
    assert a.completions == b.completions
    assert a.completions[-1][1] == "1"  # straggler observed last
    assert a.clock == a.completions[-1][0]

    # equal latencies: the seeded permutation still fixes a reproducible
    # tie-break order
    c, d = mk(5, None), mk(5, None)
    c.run_jobs(names, [[3, 3, 3, 3]] * q)
    d.run_jobs(names, [[3, 3, 3, 3]] * q)
    assert c.completions == d.completions


# ---------------------------------------------------------------------------
# ReplicaDispatcher.balance_fleet threading
# ---------------------------------------------------------------------------


def test_balance_fleet_pipeline_threading_and_warm_toggle():
    from repro.runtime.serve_loop import ReplicaDispatcher

    base = [4e-4, 2e-4, 8e-4, 3e-4]

    def replica_run(i, x):
        t = x * base[i]
        if x > 30:
            t += (x - 30) * base[i] * 3.0
        return t

    tenants = {"chat": 48, "embed": 96}
    with jax.enable_x64():
        sync = ReplicaDispatcher(replica_run, 4, eps=0.15)
        res_s = sync.balance_fleet(tenants, backend="jax", min_units=1)
        disp = ReplicaDispatcher(replica_run, 4, eps=0.15)
        res_p = disp.balance_fleet(
            tenants, backend="jax", min_units=1, pipeline=True, pipeline_depth=0
        )
        assert disp.fleet.pipeline and disp.fleet.pipeline_depth == 0
        for nm in tenants:
            assert res_p[nm].allocations == res_s[nm].allocations
            assert (
                res_p[nm].diagnostics["history"]
                == res_s[nm].diagnostics["history"]
            )
        # warm toggle back to sync drains the pipeline in place
        res_off = disp.balance_fleet(tenants, backend="jax", min_units=1)
        assert disp.fleet.pipeline is False
        assert disp.fleet._predispatched is None
        assert disp.fleet._stacked_stale is None
        for nm in tenants:
            assert sum(res_off[nm].allocations) == tenants[nm]
        # depth 1 keeps serving the warm session too
        res_d1 = disp.balance_fleet(
            tenants, backend="jax", min_units=1, pipeline=True, pipeline_depth=1
        )
        for nm in tenants:
            assert sum(res_d1[nm].allocations) == tenants[nm]
