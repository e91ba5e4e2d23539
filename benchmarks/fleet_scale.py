"""Fleet-scale multi-tenant rounds: stacked driver vs q sequential loops.

The ``FleetScheduler`` claim is economic: q concurrent jobs' rounds cost
ONE stacked partition program (plus, while still measuring, one stacked
fold-in program), where q independent ``Scheduler`` sessions pay q (resp.
2q) device dispatches for the same work.  Two regimes are measured per
(q, p), both post-compile medians:

  * **measurement rounds** (``fleet_round_ms`` / ``seq_round_ms``) — the
    DFPA loop while estimates are still being built: stacked repartition +
    batched measurement + stacked fold-in for ALL q jobs, vs q independent
    jax-backend ``SpeedStore`` sessions (a noisy executor keeps every job
    measuring every round; the fold keeps growing the banks, so this
    regime is partly compute-bound);
  * **steady-state rebalance rounds** (``rebalance_*`` columns) — the
    serving end state the paper targets ("partial estimates sufficient for
    a given accuracy"): models frozen, tenant loads drift every round, and
    the per-round work is re-partitioning everyone —
    ``FleetScheduler.rebalance`` (one stacked program) vs q per-store
    partitions.  This is the dispatch-bound regime where batching pays;
  * **pipelined serving epochs** (``pipeline_*`` columns) — the same
    steady state run as full rebalance+observe epochs, sync vs
    ``pipeline=True`` depth 1: the sync epoch serializes the fold with the
    next partition (the partition reads the carry the fold writes), the
    pipelined epoch partitions against the double-buffered previous carry
    and pre-dispatches the next epoch's partition from ``observe`` while
    the fold is in flight, so both device programs overlap the inter-epoch
    host work.  Gated: the pipelined epoch must beat sync at q >= 16,
    p=100 (and at the q=8 quick-mode smoke row), with a 3-attempt median
    retry guarding every wall-clock gate against host-profile jitter.

Sweeps q ∈ {1..64} at p=100 and p ∈ {1000, 10000} at q=16 (full mode).

Hierarchical rows (full mode): the same (q=16, p ∈ {1000, 10000}) sweep
re-run with ``groups=`` set (two-level repartition: host outer solve on the
cached ``[g, k_g]`` aggregates + one cache-blocked inner program per job)
— the p=10^4 row is where the flat stacked ``[q, p, k]`` program falls out
of CPU cache and loses to sequential (the seed measured 0.45x); the
two-level route must recover it to >= 1.0x (gated).

Acceptance gates (exit 1):
  * full mode — at every q >= 16: the stacked driver issues >= q x fewer
    device dispatches per round (all p), and the steady-state rebalance
    round is >= 2.5x faster wall-clock in the dispatch-bound regime (p=100
    rows; at p >= 1000 a CPU host is bound by the same bisection flops on
    both sides and the ratio converges to ~1x — reported, not gated);
    PLUS the hierarchical recovery gate: the hier measurement round at
    (q=16, p=10000) must be >= 1.0x vs sequential;
  * quick mode (the CI smoke) — stacked-vs-sequential ALLOCATION PARITY at
    q=8 / p=100: a noise-free fleet must reproduce q independent
    ``Scheduler.autotune`` loops bit-for-bit (allocations, histories,
    folded estimates), plus the dispatch-ratio gate at q=8, PLUS the
    pipeline-vs-sync bit-parity gate (depth 0 and depth 1 reproduce the
    sync fleet bit-for-bit on the deterministic run) and the flaky-guarded
    pipelined-epoch wall-clock smoke at q=8, PLUS the
    hierarchical consistency gate: a single-group hier fleet reproduces
    the flat fleet bit-for-bit and a multi-group hier fleet converges to a
    makespan within 5% of flat, PLUS the lane-bucket gate: a
    ``lane_buckets=True`` fleet stays bit-identical to an unbucketed one
    and an admit within a power-of-two bucket reuses both compiled device
    programs (zero recompiles).

Results are written to ``BENCH_fleet.json``.

    PYTHONPATH=src python benchmarks/fleet_scale.py [--quick] [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax

from repro.compile_cache import use_compile_cache
from repro.core import (
    BatchedSimulatedExecutor2D,
    PiecewiseLinearFPM,
    Scheduler,
    SimulatedExecutor,
    SpeedStore,
)
from repro.fleet import FleetScheduler, JobSpec


def make_tenants(q: int, p: int, seed: int = 0):
    """q tenants on one p-processor fleet: per-(job, proc) plateau/knee
    ground truth (the partition_scale fleet shape, one per tenant) plus
    6-point warm banks sampled from it."""
    rng = np.random.default_rng(seed)
    base = rng.uniform(1e-6, 3e-6, (q, p))
    knee = rng.uniform(2e3, 2e4, (q, p))

    def time_fn(X):  # X[q, p] -> T[q, p]
        return X * base * (1.0 + np.where(X > knee, 3.0 * (X - knee) / knee, 0.0))

    warm = []
    for j in range(q):
        models = []
        for i in range(p):
            xs = np.geomspace(16.0, 8.0 * knee[j, i], 6)
            ts = xs * base[j, i] * (
                1.0 + np.where(xs > knee[j, i], 3.0 * (xs - knee[j, i]) / knee[j, i], 0.0)
            )
            models.append(PiecewiseLinearFPM.from_points(list(zip(xs, xs / ts))))
        warm.append(models)
    return time_fn, warm, base, knee


def steady_state_rounds(q, p, *, rounds, warmup, seed=0, groups=None):
    """Median per-round wall-clock + dispatch counts for both drivers."""
    time_fn, warm, base, knee = make_tenants(q, p, seed=seed)
    ns = [100 * p + 7 * j for j in range(q)]
    names = [f"t{j}" for j in range(q)]

    # --- the stacked fleet driver ------------------------------------------
    fleet = FleetScheduler(p, backend="jax", groups=groups)
    for j in range(q):
        fleet.admit(
            JobSpec(name=names[j], n=ns[j], eps=1e-12, min_units=1,
                    max_iter=10**9, probe_budget=10**9),
            models=warm[j],
        )
    ex = BatchedSimulatedExecutor2D(
        time_fn_batch_2d=time_fn, p=p, q=q, job_names=names,
        noise=0.02, rng=np.random.default_rng(seed + 1),
    )

    # --- q sequential jax sessions (the pre-fleet pattern) -----------------
    stores = [
        SpeedStore.from_models(
            [PiecewiseLinearFPM.from_points(m.as_points()) for m in warm[j]],
            backend="jax",
        )
        for j in range(q)
    ]
    rng = np.random.default_rng(seed + 2)
    seq_dispatch = 2 * q  # one partition + one fold per job per round

    def seq_round():
        for j in range(q):
            d = stores[j].partition_units(ns[j], min_units=1)
            x = np.asarray(d, dtype=np.float64)
            t = x * base[j] * (
                1.0 + np.where(x > knee[j], 3.0 * (x - knee[j]) / knee[j], 0.0)
            )
            t = np.where(x > 0, np.maximum(
                t * (1.0 + 0.02 * rng.standard_normal(p)), 1e-12), 0.0)
            s = np.where((x > 0) & (t > 0), x / np.where(t > 0, t, 1.0), 1.0)
            stores[j].fold_in(x, s, (x > 0) & (t > 0))

    # Interleaved per-round timing (the partition_scale best_of_pair
    # convention): both drivers advance one round back-to-back, so
    # shared-container load drift hits the pair together and the MEDIAN of
    # per-round ratios stays honest even when absolute times wander.
    fleet_times, seq_times, ratios = [], [], []
    for r in range(warmup + rounds):
        t0 = time.perf_counter()
        fleet.step(ex)
        tf = time.perf_counter() - t0
        t0 = time.perf_counter()
        seq_round()
        tsq = time.perf_counter() - t0
        if r >= warmup:
            fleet_times.append(tf)
            seq_times.append(tsq)
            ratios.append(tsq / tf)
    assert len(fleet.active_jobs) == q, "benchmark jobs must not converge"
    fleet_dispatch = fleet.device_dispatches / fleet.rounds

    return {
        "q": q,
        "p": p,
        "n_per_job": ns[0],
        "rounds_timed": rounds,
        "fleet_round_ms": float(np.median(fleet_times) * 1e3),
        "seq_round_ms": float(np.median(seq_times) * 1e3),
        "wallclock_speedup": float(np.median(ratios)),
        "fleet_dispatches_per_round": fleet_dispatch,
        "seq_dispatches_per_round": float(seq_dispatch),
        "dispatch_ratio": seq_dispatch / fleet_dispatch,
    }


def rebalance_rounds(q, p, *, rounds, warmup, seed=0, groups=None):
    """The serving steady state: tenant models already learned (the paper's
    'partial estimates sufficient for a given accuracy'), per-round work is
    re-partitioning everyone under drifting loads — ``FleetScheduler.
    rebalance`` (ONE stacked program) vs q per-store partitions.  This is
    the dispatch-bound regime the wall-clock gate runs on."""
    _, warm, _, _ = make_tenants(q, p, seed=seed)
    ns = [100 * p + 7 * j for j in range(q)]
    names = [f"t{j}" for j in range(q)]

    fleet = FleetScheduler(p, backend="jax", groups=groups)
    for j in range(q):
        fleet.admit(
            JobSpec(name=names[j], n=ns[j], eps=1e-12, min_units=1),
            models=warm[j],
        )

    def loads(r):
        return {
            names[j]: ns[j] + ((r * 29 + j * 13) % max(7, p // 10))
            for j in range(q)
        }

    stores = [
        SpeedStore.from_models(
            [PiecewiseLinearFPM.from_points(m.as_points()) for m in warm[j]],
            backend="jax",
        )
        for j in range(q)
    ]

    # Interleaved, same rationale as the measurement rounds above.
    d0 = fleet.device_dispatches
    fleet_times, seq_times, ratios = [], [], []
    for r in range(warmup + rounds):
        ld = loads(r)
        t0 = time.perf_counter()
        fleet.rebalance(ld)
        tf = time.perf_counter() - t0
        t0 = time.perf_counter()
        for j in range(q):
            stores[j].partition_units(ld[names[j]], min_units=1)
        tsq = time.perf_counter() - t0
        if r >= warmup:
            fleet_times.append(tf)
            seq_times.append(tsq)
            ratios.append(tsq / tf)
    fleet_dispatch = (fleet.device_dispatches - d0) / (warmup + rounds)

    return {
        "rebalance_fleet_ms": float(np.median(fleet_times) * 1e3),
        "rebalance_seq_ms": float(np.median(seq_times) * 1e3),
        "rebalance_speedup": float(np.median(ratios)),
        "rebalance_fleet_dispatches_per_round": fleet_dispatch,
        "rebalance_seq_dispatches_per_round": float(q),
        "rebalance_dispatch_ratio": q / fleet_dispatch,
    }


def pipeline_rounds(q, p, *, rounds, warmup, seed=0, depth=1):
    """Steady-state serving epochs under a FIXED tenancy, sync vs
    ``pipeline=True``: each epoch is ``rebalance()`` (one stacked
    partition) + ``observe(times)`` (one stacked fold-in).  The sync epoch
    serializes — its partition reads the carry the previous epoch's fold
    writes, so the timed ``rebalance`` waits for the fold before the
    partition even starts.  The depth-1 pipeline partitions against the
    double-buffered PREVIOUS carry (a speculative read, validated against
    the seen sets — serving tenants admitted with learned models never
    populate them, so every read is consumed) and ``observe`` pre-dispatches
    the next epoch's partition while its fold is still in flight: the next
    ``rebalance`` only fetches, and both device programs overlap the
    inter-epoch host work.  Interleaved per-epoch timing, same convention
    as the other regimes."""
    _, warm, base, knee = make_tenants(q, p, seed=seed)
    ns = [100 * p + 7 * j for j in range(q)]
    names = [f"t{j}" for j in range(q)]

    def mk(pipeline):
        fleet = FleetScheduler(
            p, backend="jax", pipeline=pipeline, pipeline_depth=depth
        )
        for j in range(q):
            fleet.admit(
                JobSpec(name=names[j], n=ns[j], eps=1e-12, min_units=1),
                models=warm[j],
            )
        return fleet

    def times_for(ds, rng):
        out = {}
        for j, nm in enumerate(names):
            x = np.asarray(ds[nm], dtype=np.float64)
            t = x * base[j] * (
                1.0 + np.where(x > knee[j], 3.0 * (x - knee[j]) / knee[j], 0.0)
            )
            t = np.where(x > 0, np.maximum(
                t * (1.0 + 0.02 * rng.standard_normal(p)), 1e-12), 0.0)
            out[nm] = [float(v) for v in t]
        return out

    sync, pipe = mk(False), mk(True)
    # identical noise streams: the two fleets see the same observations as
    # long as their trajectories agree, so the comparison stays apples to
    # apples even though wall-clock is the only gated quantity
    rng_s = np.random.default_rng(seed + 5)
    rng_p = np.random.default_rng(seed + 5)
    sync_times, pipe_times, ratios = [], [], []
    for r in range(warmup + rounds):
        t0 = time.perf_counter()
        ds = sync.rebalance()
        sync.observe(times_for(ds, rng_s))
        ts = time.perf_counter() - t0
        t0 = time.perf_counter()
        dp = pipe.rebalance()
        pipe.observe(times_for(dp, rng_p))
        tp = time.perf_counter() - t0
        if r >= warmup:
            sync_times.append(ts)
            pipe_times.append(tp)
            ratios.append(ts / tp)
    return {
        "pipeline_round_ms": float(np.median(pipe_times) * 1e3),
        "pipeline_sync_round_ms": float(np.median(sync_times) * 1e3),
        "pipeline_speedup": float(np.median(ratios)),
        "pipeline_stale_reads": pipe.stale_reads,
        "pipeline_speculative_misses": pipe.speculative_misses,
        "pipeline_predispatches": pipe.predispatches,
    }


def obs_overhead_rounds(q, p, *, rounds, warmup, seed=0, tel=None):
    """ENABLED-telemetry overhead on serving epochs: two identical fleets
    (same warm models, same noise stream) advance interleaved — one under an
    installed ``repro.obs.Telemetry`` sink, one under the default no-op —
    and the gated metric is the median per-epoch wall ratio
    disabled/enabled (``obs_speedup``; 1.0 = free, the gate holds it
    >= 0.98, i.e. enabled within 2% of disabled).  The telemetry sink is
    ring-bounded so the recording itself cannot grow the round."""
    from repro import obs

    if tel is None:
        tel = obs.Telemetry(capacity=8192)
    _, warm, base, knee = make_tenants(q, p, seed=seed)
    ns = [100 * p + 7 * j for j in range(q)]
    names = [f"t{j}" for j in range(q)]

    def mk():
        fleet = FleetScheduler(p, backend="jax")
        for j in range(q):
            fleet.admit(
                JobSpec(name=names[j], n=ns[j], eps=1e-12, min_units=1),
                models=[
                    PiecewiseLinearFPM.from_points(m.as_points())
                    for m in warm[j]
                ],
            )
        return fleet

    def times_for(ds, rng):
        out = {}
        for j, nm in enumerate(names):
            x = np.asarray(ds[nm], dtype=np.float64)
            t = x * base[j] * (
                1.0 + np.where(x > knee[j], 3.0 * (x - knee[j]) / knee[j], 0.0)
            )
            t = np.where(x > 0, np.maximum(
                t * (1.0 + 0.02 * rng.standard_normal(p)), 1e-12), 0.0)
            out[nm] = [float(v) for v in t]
        return out

    on, off = mk(), mk()
    rng_on = np.random.default_rng(seed + 9)
    rng_off = np.random.default_rng(seed + 9)
    on_times, off_times, ratios = [], [], []
    for r in range(warmup + rounds):
        obs.install(tel)
        try:
            t0 = time.perf_counter()
            ds = on.rebalance()
            on.observe(times_for(ds, rng_on))
            t_on = time.perf_counter() - t0
        finally:
            obs.uninstall()
        t0 = time.perf_counter()
        ds = off.rebalance()
        off.observe(times_for(ds, rng_off))
        t_off = time.perf_counter() - t0
        if r >= warmup:
            on_times.append(t_on)
            off_times.append(t_off)
            ratios.append(t_off / t_on)
    return {
        "obs_q": q,
        "obs_p": p,
        "obs_enabled_round_ms": float(np.median(on_times) * 1e3),
        "obs_disabled_round_ms": float(np.median(off_times) * 1e3),
        "obs_speedup": float(np.median(ratios)),
        "obs_events_recorded": len(tel.events),
    }


def _median_retry(measure, metric_key, threshold, attempts=3):
    """Flaky-guard for wall-clock gates: measure once; only when the gated
    metric misses ``threshold`` re-measure (``attempts`` total) and keep
    the attempt with the MEDIAN metric.  One jittery round on a loaded CI
    host can no longer fail a parity-correct build — and cannot rescue a
    genuinely slow one either, since the median of three must pass (the
    PR 6 recalibration note made host-profile jitter a known hazard)."""
    row = measure(0)
    row["attempts"] = 1
    if row[metric_key] >= threshold:
        return row
    rows = [row] + [measure(a) for a in range(1, attempts)]
    rows.sort(key=lambda r: r[metric_key])
    row = rows[len(rows) // 2]
    row["attempts"] = attempts
    return row


def pipeline_parity_gate(q=8, p=100, seed=17) -> bool:
    """pipeline-vs-sync bit-parity (the CI smoke): on a deterministic
    measuring fleet every depth-1 speculation misses its seen-set
    validation, so the pipelined autotune trajectory must reproduce the
    sync fleet bit-for-bit at depth 0 AND depth 1 (the 200-case fuzz
    battery lives in tests/test_fleet_pipeline.py)."""
    rng = np.random.default_rng(seed)
    base = rng.uniform(1e-5, 9e-5, (q, p))
    knee = rng.uniform(50.0, 500.0, (q, p))

    def batch_fn(X):
        return X * base * (1.0 + np.where(X > knee, 3.0 * (X - knee) / knee, 0.0))

    ns = [20 * p + 13 * j for j in range(q)]
    names = [f"t{j}" for j in range(q)]

    def run(pipeline, pipeline_depth):
        fleet = FleetScheduler(
            p, backend="jax", pipeline=pipeline, pipeline_depth=pipeline_depth
        )
        for j in range(q):
            fleet.admit(JobSpec(name=names[j], n=ns[j], eps=0.03, min_units=1,
                                max_iter=8))
        ex = BatchedSimulatedExecutor2D(
            time_fn_batch_2d=batch_fn, p=p, q=q, job_names=names
        )
        return fleet.run(ex)

    sync = run(False, 1)
    ok = True
    for pipeline_depth in (0, 1):
        piped = run(True, pipeline_depth)
        for nm in names:
            r_p, r_s = piped[nm], sync[nm]
            if (
                r_p.allocations != r_s.allocations
                or r_p.times != r_s.times
                or r_p.diagnostics["history"] != r_s.diagnostics["history"]
            ):
                print(f"PIPELINE PARITY FAIL: job {nm} diverges from sync "
                      f"at depth {pipeline_depth}")
                ok = False
    return ok


def parity_gate(q=8, p=100, seed=11) -> bool:
    """Noise-free fleet vs q independent Scheduler.autotune loops: the
    bit-identity contract the CI smoke enforces (the full fuzz battery
    lives in tests/test_fleet.py)."""
    rng = np.random.default_rng(seed)
    base = rng.uniform(1e-5, 9e-5, (q, p))
    knee = rng.uniform(50.0, 500.0, (q, p))

    def batch_fn(X):
        return X * base * (1.0 + np.where(X > knee, 3.0 * (X - knee) / knee, 0.0))

    ns = [20 * p + 13 * j for j in range(q)]
    ok = True
    indep = []
    for j in range(q):
        fns = [
            (lambda b, k: lambda x: float(
                x * b * (1.0 + (3.0 * (x - k) / k if x > k else 0.0))
            ))(base[j, i], knee[j, i])
            for i in range(p)
        ]
        ex = SimulatedExecutor(time_fns=fns)
        sched = Scheduler(SpeedStore.empty(p, backend="jax"), backend="jax")
        indep.append(sched.autotune(ex, ns[j], 0.03, max_iter=8, min_units=1))
    fleet = FleetScheduler(p, backend="jax")
    names = [f"t{j}" for j in range(q)]
    for j in range(q):
        fleet.admit(JobSpec(name=names[j], n=ns[j], eps=0.03, min_units=1,
                            max_iter=8))
    ex2 = BatchedSimulatedExecutor2D(
        time_fn_batch_2d=batch_fn, p=p, q=q, job_names=names
    )
    results = fleet.run(ex2)
    for j in range(q):
        r_f, r_i = results[names[j]], indep[j]
        if (
            r_f.allocations != r_i.allocations
            or r_f.times != r_i.times
            or r_f.diagnostics["history"] != r_i.diagnostics["history"]
        ):
            print(f"PARITY FAIL: job {names[j]} diverges from its "
                  f"independent Scheduler.autotune loop")
            ok = False
    return ok


def hier_parity_gate(q=4, p=100, seed=23) -> bool:
    """The hierarchical consistency contract (the CI smoke):

    * a SINGLE-group hier fleet must reproduce the flat fleet bit-for-bit
      (the outer level degenerates to "one group takes all n");
    * a MULTI-group hier fleet (4 groups of 25) must converge every job to
      a makespan within 5% of the flat fleet's.
    """
    rng = np.random.default_rng(seed)
    base = rng.uniform(1e-5, 9e-5, (q, p))
    knee = rng.uniform(50.0, 500.0, (q, p))

    def batch_fn(X):
        return X * base * (1.0 + np.where(X > knee, 3.0 * (X - knee) / knee, 0.0))

    ns = [20 * p + 13 * j for j in range(q)]
    names = [f"t{j}" for j in range(q)]

    def run(groups):
        fleet = FleetScheduler(p, backend="jax", groups=groups)
        for j in range(q):
            fleet.admit(JobSpec(name=names[j], n=ns[j], eps=0.03,
                                min_units=1, max_iter=8))
        ex = BatchedSimulatedExecutor2D(
            time_fn_batch_2d=batch_fn, p=p, q=q, job_names=names
        )
        return fleet.run(ex)

    flat = run(None)
    hier1 = run([0] * p)
    hier4 = run([i % 4 for i in range(p)])
    ok = True
    for j, nm in enumerate(names):
        if hier1[nm].allocations != flat[nm].allocations:
            print(f"HIER PARITY FAIL: single-group fleet diverges from flat "
                  f"for job {nm}")
            ok = False
        m_flat, m_hier = flat[nm].makespan, hier4[nm].makespan
        if not (m_hier <= m_flat * 1.05 + 1e-12):
            print(f"HIER PARITY FAIL: multi-group makespan {m_hier:.4f} vs "
                  f"flat {m_flat:.4f} for job {nm}")
            ok = False
        if sum(hier4[nm].allocations) != ns[j]:
            print(f"HIER PARITY FAIL: multi-group allocations of {nm} do not "
                  f"sum to n")
            ok = False
    return ok


def bucket_gate(p=50, seed=41) -> bool:
    """Lane-bucket contract (the CI smoke): with ``lane_buckets=True`` the
    jax stack is padded to the next power-of-two lane count with masked
    dead lanes, so (a) allocations stay bit-identical to the unbucketed
    fleet, and (b) admitting a tenant WITHIN the bucket reuses both
    compiled device programs — zero recompiles across the admit."""
    from repro.core import modelbank_jax as mbj

    _, warm, _, _ = make_tenants(4, p, seed=seed)
    ns = [100 * p + 7 * j for j in range(4)]
    names = [f"t{j}" for j in range(4)]

    def mk(buckets):
        fl = FleetScheduler(p, backend="jax", reserve_knots=16,
                            lane_buckets=buckets)
        for j in range(3):
            fl.admit(JobSpec(name=names[j], n=ns[j], eps=1e-12, min_units=1),
                     models=warm[j])
        return fl

    plain, bucketed = mk(False), mk(True)
    ok = True
    if plain.rebalance() != bucketed.rebalance():
        print("BUCKET FAIL: bucketed fleet diverges from plain at q=3")
        ok = False
    if int(bucketed._stacked.counts.shape[0]) != 4:
        print("BUCKET FAIL: q=3 stack not padded to 4 lanes")
        ok = False

    # Warm BOTH device programs at the padded shape before taking the
    # cache baseline — the fold program only compiles on first observe.
    obs = {names[0]: [0.1 * (i + 1) for i in range(p)]}
    bucketed.observe(obs)
    bucketed.rebalance()
    c0 = mbj._partition_units_jit._cache_size()
    f0 = mbj._fold_in_jit._cache_size()
    bucketed.admit(JobSpec(name=names[3], n=ns[3], eps=1e-12, min_units=1),
                   models=warm[3])
    ds = bucketed.rebalance()
    bucketed.observe({names[3]: [0.1 * (i + 1) for i in range(p)]})
    dc = mbj._partition_units_jit._cache_size() - c0
    df = mbj._fold_in_jit._cache_size() - f0
    if dc or df:
        print(f"BUCKET FAIL: admit within the 4-lane bucket recompiled "
              f"(partition +{dc}, fold +{df})")
        ok = False
    if sum(ds[names[3]]) != ns[3]:
        print("BUCKET FAIL: padded-lane allocations do not sum to n")
        ok = False

    # Parity must survive the admit too (plain replays the same fold).
    plain.observe(obs)
    plain.admit(JobSpec(name=names[3], n=ns[3], eps=1e-12, min_units=1),
                models=warm[3])
    if plain.rebalance() != ds:
        print("BUCKET FAIL: bucketed fleet diverges from plain after admit")
        ok = False
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="CI smoke: parity gate + small sweep")
    ap.add_argument("--out", default="BENCH_fleet.json")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="export the obs-overhead regime's telemetry as a "
                         "Chrome-trace JSON (open in chrome://tracing)")
    args = ap.parse_args(argv)
    use_compile_cache()
    # Bit-identical-to-sequential is the parity gate; that needs doubles.
    jax.config.update("jax_enable_x64", True)

    if args.quick:
        sweep = [(1, 100), (8, 100)]
        rounds, warmup = args.rounds or 5, 3
    else:
        sweep = [(1, 100), (2, 100), (4, 100), (8, 100), (16, 100),
                 (32, 100), (64, 100), (16, 1000), (16, 10000)]
        rounds, warmup = args.rounds or 8, 3

    if args.quick:
        hier_sweep = []
    else:
        # re-run the q=16 large-p rows through the two-level route; p=10^4
        # is the cache-wall row the recovery gate runs on
        hier_sweep = [(16, 1000, 100), (16, 10000, 1000)]

    rows = []
    for q, p in sweep:
        row = steady_state_rounds(q, p, rounds=rounds, warmup=warmup, seed=q * 1000 + p)
        # the 2.5x wall-clock gate runs on these rows: flaky-guarded
        gated_wallclock = q >= 16 and p <= 100
        if gated_wallclock:
            row.update(_median_retry(
                lambda a: rebalance_rounds(
                    q, p, rounds=rounds, warmup=warmup, seed=q * 1000 + p + 1 + a
                ),
                "rebalance_speedup", 2.5,
            ))
        else:
            row.update(rebalance_rounds(
                q, p, rounds=rounds, warmup=warmup, seed=q * 1000 + p + 1
            ))
        # pipelined serving epochs vs sync (gated below sync at q >= 16
        # and, in quick mode, at the q=8 smoke row — both flaky-guarded)
        if gated_wallclock or (args.quick and q >= 8):
            row.update(_median_retry(
                lambda a: pipeline_rounds(
                    q, p, rounds=rounds, warmup=warmup, seed=q * 1000 + p + 2 + a
                ),
                "pipeline_speedup", 1.0,
            ))
        else:
            row.update(pipeline_rounds(
                q, p, rounds=rounds, warmup=warmup, seed=q * 1000 + p + 2
            ))
        rows.append(row)
        print(
            f"q={q:3d} p={p:6d}"
            f"  measure {row['fleet_round_ms']:8.2f} vs {row['seq_round_ms']:8.2f} ms"
            f" ({row['wallclock_speedup']:5.2f}x)"
            f"  rebalance {row['rebalance_fleet_ms']:8.2f} vs "
            f"{row['rebalance_seq_ms']:8.2f} ms ({row['rebalance_speedup']:5.2f}x)"
            f"  pipeline {row['pipeline_round_ms']:8.2f} vs "
            f"{row['pipeline_sync_round_ms']:8.2f} ms "
            f"({row['pipeline_speedup']:5.2f}x)"
            f"  dispatches {row['fleet_dispatches_per_round']:.1f} vs "
            f"{row['seq_dispatches_per_round']:.0f}"
            f" ({row['dispatch_ratio']:5.1f}x fewer)",
            flush=True,
        )

    for q, p, gsize in hier_sweep:
        groups = [i // gsize for i in range(p)]
        row = steady_state_rounds(
            q, p, rounds=rounds, warmup=warmup, seed=q * 1000 + p, groups=groups
        )
        row.update(
            rebalance_rounds(
                q, p, rounds=rounds, warmup=warmup, seed=q * 1000 + p + 1,
                groups=groups,
            )
        )
        row["hier"] = True
        row["group_size"] = gsize
        rows.append(row)
        print(
            f"q={q:3d} p={p:6d} HIER(g={p // gsize})"
            f"  measure {row['fleet_round_ms']:8.2f} vs {row['seq_round_ms']:8.2f} ms"
            f" ({row['wallclock_speedup']:5.2f}x)"
            f"  rebalance {row['rebalance_fleet_ms']:8.2f} vs "
            f"{row['rebalance_seq_ms']:8.2f} ms ({row['rebalance_speedup']:5.2f}x)",
            flush=True,
        )

    # Telemetry overhead: ENABLED recording must stay within 2% of the
    # disabled no-op on a serving epoch (flaky-guarded like every other
    # wall-clock gate).  Quick mode runs it at the q=8 smoke row.
    from repro import obs
    from repro.obs.chrometrace import export_chrome_trace

    oq, op = (8, 100) if args.quick else (16, 100)
    obs_tel = obs.Telemetry(capacity=8192)
    print(f"telemetry overhead (q={oq}, p={op}, enabled vs disabled) ...",
          flush=True)
    obs_row = _median_retry(
        lambda a: obs_overhead_rounds(
            oq, op, rounds=rounds, warmup=warmup,
            seed=oq * 1000 + op + 5 + a, tel=obs_tel,
        ),
        "obs_speedup", 0.98,
    )
    print(f"  enabled {obs_row['obs_enabled_round_ms']:.2f} ms vs disabled "
          f"{obs_row['obs_disabled_round_ms']:.2f} ms "
          f"({obs_row['obs_speedup']:.3f}x, "
          f"{obs_row['obs_events_recorded']} events)", flush=True)
    if args.trace:
        export_chrome_trace(obs_tel, args.trace)
        print(f"-> {args.trace}")

    print("parity gate (q=8, p=100, noise-free) ...", flush=True)
    parity_ok = parity_gate()
    print("parity:", "OK" if parity_ok else "FAIL")

    print("pipeline parity gate (q=8, p=100, depth 0 and 1) ...", flush=True)
    pipeline_ok = pipeline_parity_gate()
    print("pipeline parity:", "OK" if pipeline_ok else "FAIL")

    print("hier consistency gate (q=4, p=100, noise-free) ...", flush=True)
    hier_ok = hier_parity_gate()
    print("hier consistency:", "OK" if hier_ok else "FAIL")

    print("lane-bucket gate (q=3->4 lanes, p=50, zero recompiles) ...",
          flush=True)
    bucket_ok = bucket_gate()
    print("lane buckets:", "OK" if bucket_ok else "FAIL")

    payload = {
        "benchmark": "fleet_scale",
        "description": (
            "multi-tenant rounds, FleetScheduler vs q independent "
            "jax-backend sessions: measurement rounds (stacked [q,p,k] "
            "partition + fold-in = 2 programs/round vs 2q; 2% noise keeps "
            "every job measuring, so banks keep growing and large p turns "
            "compute-bound — and at p=10^4 the q-wide [q,p,k] working set "
            "falls out of CPU cache, so the stacked measurement round can "
            "even lose to sequential there) and steady-state rebalance "
            "rounds (models frozen, loads drift: FleetScheduler.rebalance "
            "= 1 program vs q — the dispatch-bound serving regime the >=2.5x "
            "wall-clock gate runs on at p=100) and pipelined serving epochs "
            "(rebalance+observe per epoch, sync vs pipeline=True depth 1: "
            "double-buffered carry + pre-dispatched next partition overlap "
            "the fold and the inter-epoch host work — gated below sync at "
            "q>=16, p=100, 3-attempt median retry on every wall-clock "
            "gate); medians post-compile, "
            "fleet/sequential rounds interleaved so shared-runner load "
            "drift hits both together (speedup = median per-round ratio); "
            "parity = "
            "noise-free fleet reproduces q independent Scheduler.autotune "
            "loops bit-for-bit"
        ),
        "rounds_timed": rounds,
        "parity_q8_p100": parity_ok,
        "pipeline_parity_q8_p100": pipeline_ok,
        "hier_parity_q4_p100": hier_ok,
        "bucket_q3_p50": bucket_ok,
        "sweep": rows,
        "obs_overhead": obs_row,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"-> {args.out}")

    rc = 0
    if not parity_ok:
        rc = 1
    if not pipeline_ok:
        print("FAIL: pipelined fleet diverges from sync on the deterministic "
              "parity run at q=8, p=100")
        rc = 1
    if not hier_ok:
        print("FAIL: hierarchical route diverges from flat at q=4, p=100")
        rc = 1
    if not bucket_ok:
        print("FAIL: lane buckets diverge from plain or recompile within "
              "a bucket at q=3->4, p=50")
        rc = 1
    if obs_row["obs_speedup"] < 0.98:
        print(f"FAIL: ENABLED telemetry costs more than 2% of a serving "
              f"epoch at q={obs_row['obs_q']}, p={obs_row['obs_p']} "
              f"({obs_row['obs_speedup']:.3f}x vs >= 0.98x)")
        rc = 1
    for row in rows:
        if row.get("hier"):
            # Hier rows deliberately trade per-round dispatches (one extra
            # outer program per lane) for inner cache locality — they are
            # gated on wall-clock recovery below, not on dispatch ratios.
            continue
        if row["q"] >= 16:
            if (
                row["dispatch_ratio"] < row["q"]
                or row["rebalance_dispatch_ratio"] < row["q"]
            ):
                print(f"FAIL: dispatch ratio {row['dispatch_ratio']:.1f}x < "
                      f"q={row['q']} at p={row['p']}")
                rc = 1
            # Wall-clock gate runs on the dispatch-bound serving regime
            # (steady-state rebalance rounds at p=100).  At p >= 1000 on a
            # CPU host both sides are bound by the SAME bisection flops and
            # converge to ~1x — reported, not gated; a real accelerator's
            # dispatch overhead is where the stacked win grows (ROADMAP:
            # real-TPU fleet lane).  The threshold is host-profile
            # dependent: the sequential side is pure per-program dispatch
            # overhead x q, so hosts with cheap dispatch compress the ratio
            # (one recorded host measures 4.0-4.5x, another 2.8x on the
            # IDENTICAL code).  2.5x guards the "multiples faster" claim
            # across profiles.
            if row["p"] <= 100 and row["rebalance_speedup"] < 2.5:
                print(f"FAIL: steady-state rebalance speedup "
                      f"{row['rebalance_speedup']:.2f}x < 2.5x at q={row['q']}, "
                      f"p={row['p']}")
                rc = 1
            # The pipelined serving epoch must beat the sync epoch where
            # dispatch overlap pays (the serialized fold->partition wait is
            # per-round overhead at every p, but gated on the same
            # dispatch-bound rows as the rebalance gate; flaky-guarded by
            # the 3-attempt median retry above).
            if row["p"] <= 100 and row["pipeline_speedup"] < 1.0:
                print(f"FAIL: pipelined round {row['pipeline_round_ms']:.2f} ms "
                      f"not below sync {row['pipeline_sync_round_ms']:.2f} ms "
                      f"at q={row['q']}, p={row['p']} "
                      f"({row['pipeline_speedup']:.2f}x)")
                rc = 1
    # Recovery gate: the hierarchical route must break the p=10^4 cache
    # wall — the seed flat stacked round lost to sequential there (0.45x);
    # two-level with cache-blocked inner groups must be >= 1.0x.
    for row in rows:
        if row.get("hier") and row["q"] == 16 and row["p"] == 10000:
            if row["wallclock_speedup"] < 1.0:
                print(f"FAIL: hier measurement round {row['wallclock_speedup']:.2f}x"
                      f" < 1.0x vs sequential at q=16, p=10^4 (cache wall "
                      f"not recovered)")
                rc = 1
    # quick mode: the dispatch economics must already show at q=8, and the
    # pipelined epoch must not lose to sync (flaky-guarded wall-clock smoke)
    if args.quick:
        for row in rows:
            if row["q"] >= 8 and row["dispatch_ratio"] < row["q"]:
                print(f"FAIL: dispatch ratio {row['dispatch_ratio']:.1f}x < "
                      f"q={row['q']} in quick sweep")
                rc = 1
            if row["q"] >= 8 and row["pipeline_speedup"] < 1.0:
                print(f"FAIL: pipelined round {row['pipeline_speedup']:.2f}x "
                      f"vs sync in quick sweep at q={row['q']}")
                rc = 1
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
