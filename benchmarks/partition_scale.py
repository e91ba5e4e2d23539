"""Fleet-scale partition latency: scalar vs numpy ModelBank vs jitted jax
bank — all driven through the ``SpeedStore``/``Scheduler`` facade.

The paper's self-adaptability requirement is that computing an optimal
distribution costs orders of magnitude less than the application it balances.
This benchmark measures that cost directly for all three partition backends
on synthetic heterogeneous fleets of p ∈ {10, 100, 1000, 10000} processor
groups (HCL-like piecewise-linear FPMs, ~6 observed points each):

  * scalar — the seed implementation (``SpeedStore`` backend ``"scalar"``):
    every bisection step on ``t*`` is a p-long Python loop over per-model
    segment scans;
  * bank   — the ``ModelBank`` path: one numpy pass per bisection step;
  * jax    — the ``JaxModelBank`` path: the whole t* search + integer
    completion under ``jax.jit``.  Two numbers matter: the one-time compile
    cost, and the steady-state repartition latency afterwards.

Completion-mode columns: the synthetic fleets are monotone-time, so the
default (``completion="auto"``) routes the JAX backend through the
threshold-count completion; on the numpy host path "auto" stays on the lazy
heap (the PR 5 routing fix — ``bank_threshold_s`` records what the forced
threshold pass costs there: ~one extra continuous solve).  Each backend is
also timed with the exact per-unit completion forced (``*_exact_s``
columns).  ``jax_completion_speedup`` is
the headline ratio — at p=10^5 the sequential masked-argmin loop (~p/2
``while_loop`` iterations) is what used to block millisecond repartitioning,
and the acceptance gate requires the threshold path to beat it by >= 10x
there.  A divergence gate asserts fast-vs-exact MAKESPAN equality (and
reports allocation diffs) at every swept p; at p=1000 it is enforced in the
CI smoke (exit 1).

Facade-overhead columns: each banked backend is timed twice — as a *direct*
kernel call (``_partition_units_bank`` / ``JaxModelBank.partition_units``)
and through the facade (``SpeedStore.partition_units``: validation +
pre-resolved dispatch).  ``facade_overhead_pct`` is the facade tax; the
acceptance gate is <= 5% at p=1000 (exit 1 otherwise).

Float32 drift columns (full sweep, p=10^4 AND p=10^5): the jax backend
re-runs with a float32 bank (dtype plumbing keeps the whole jitted pipeline
in f32) and records the max/total unit drift vs the float64 numpy reference
— the data behind the ``SpeedStore(dtype=...)`` serving-fleet policy (zero
drift at p=10^4; worst case ±1 unit at p=10^5).

Hierarchical columns (p >= 1000): the same fleet solved through the
two-level ``Hierarchy`` route (groups of 100 at p=1000, 1000 above) —
``hier_s`` is the numpy inner path, ``hier_jax_s``/``hier_jax_compile_s``
the jitted block path.  The hierarchy solves an outer t* on ``g`` group
aggregates then ``g`` independent inner solves over cache-sized blocks,
trading exactness for locality: ``hier_makespan_ratio`` (two-level vs flat
makespan) is gated <= 1.12 at every swept p, matching the fuzz-test
envelope (empirical worst over 340 random monotone fleets is ~1.10).

The p=10^6 row (eight 125k-processor group banks under
``sharding="shard_map"``) runs on a four-chip host as
``chip_smoke.py --four-chip``.

The jax sweep runs with x64 enabled and asserts its allocations are
BIT-IDENTICAL to the numpy bank at every swept p (exit code 1 otherwise —
CI runs the quick sweep, so parity is enforced on every PR).

Results are written to ``BENCH_partition.json``.

    PYTHONPATH=src python benchmarks/partition_scale.py \
        [--quick] [--backend numpy|jax|both] [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import Hierarchy, ModelBank, PiecewiseLinearFPM, SpeedStore
from repro.core.partition import _partition_units_bank, _prep_unit_caps


def make_fleet(p: int, seed: int = 0):
    """p heterogeneous piecewise-linear FPMs: plateau speed spanning ~3x,
    cache boost at small x, paging-style decay past a per-proc knee."""
    rng = np.random.default_rng(seed)
    plateau = rng.uniform(1.0, 3.0, p) * 1e6
    knee = rng.uniform(2e3, 2e4, p)
    models = []
    for i in range(p):
        xs = np.geomspace(16.0, 8.0 * knee[i], 6)
        ss = np.where(
            xs <= knee[i],
            plateau[i] * (1.0 + 0.4 * np.exp(-xs / 500.0)),
            plateau[i] / (1.0 + 2.0 * (xs - knee[i]) / knee[i]),
        )
        models.append(PiecewiseLinearFPM.from_points(list(zip(xs, ss))))
    return models


def make_fleet_bank(p: int, seed: int = 0) -> ModelBank:
    """Same fleet distribution as :func:`make_fleet`, built directly as a
    ``ModelBank`` with vectorized numpy (no per-model Python objects) — the
    only way to stand up the p=10^6 row's 125k-processor group banks in
    milliseconds instead of minutes.  Draw order matches ``make_fleet`` so
    identical seeds give bit-identical fleets (parity-checked in tests)."""
    rng = np.random.default_rng(seed)
    plateau = (rng.uniform(1.0, 3.0, p) * 1e6)[:, None]
    knee = rng.uniform(2e3, 2e4, p)[:, None]
    xs = np.exp(
        np.linspace(0.0, 1.0, 6)[None, :] * (np.log(8.0 * knee) - np.log(16.0))
        + np.log(16.0)
    )
    ss = np.where(
        xs <= knee,
        plateau * (1.0 + 0.4 * np.exp(-xs / 500.0)),
        plateau / (1.0 + 2.0 * (xs - knee) / knee),
    )
    return ModelBank(xs=xs, ss=ss, counts=np.full(p, 6, dtype=np.int64))


def best_of(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def best_of_pair(fn_a, fn_b, repeats: int):
    """Interleaved timing for two implementations of the same work.

    Returns ``(best_a, best_b, ratio)`` where ``ratio`` is the MEDIAN over
    iterations of ``t_b / t_a`` *within the same iteration*.  Within one
    iteration the two sides run back-to-back, so shared-container load noise
    hits both together and their ratio stays honest even when the absolute
    best-of times land in different load windows; the median then rejects
    the iterations where a noise spike split the pair.  The facade-tax gate
    uses this ratio, not the difference of bests."""
    best_a = best_b = float("inf")
    ratios = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn_a()
        ta = time.perf_counter() - t0
        t0 = time.perf_counter()
        fn_b()
        tb = time.perf_counter() - t0
        best_a = min(best_a, ta)
        best_b = min(best_b, tb)
        ratios.append(tb / ta)
    return best_a, best_b, float(np.median(ratios))


def run_sweep(ps, repeats: int, backend: str, units_per_proc: int = 100,
              scalar_cutoff: int = 10**9, f32_ps=()):
    if backend in ("jax", "both"):
        import jax

        # Bit-identical-to-numpy is the acceptance gate; that needs doubles.
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp

        from repro.core import JaxModelBank

    rows = []
    for p in ps:
        models = make_fleet(p, seed=p)
        bank = ModelBank.from_models(models)
        bank_store = SpeedStore.from_bank(bank)
        n = units_per_proc * p
        icaps = _prep_unit_caps(p, n, None, 1)

        def makespan(d):
            return float(np.max(bank.time(np.asarray(d, dtype=np.float64))))

        # The synthetic fleets are monotone-time, so jax "auto" routes to
        # threshold-count (host "auto" stays on the heap since the PR 5
        # routing fix — the forced bank_threshold_s column is the host
        # comparison); assert it so a generator change can't silently turn
        # the completion columns into a no-op comparison.
        assert bank.is_monotone(), "benchmark fleet must be monotone-time"
        ex_reps = max(1, min(repeats, 2)) if p >= 10**5 else repeats

        # Direct kernel vs the facade (validation + pre-resolved dispatch),
        # interleaved so container-load drift cannot fake an overhead.  The
        # pair repeats adapt to a ~1s budget: small-p ops are milliseconds,
        # so dozens of samples keep the median ratio well under the shared-
        # runner noise floor that a fixed 7 would leave it exposed to.
        direct_fn = lambda: _partition_units_bank(bank, n, list(icaps), min_units=1)
        facade_fn = lambda: bank_store.partition_units(n, min_units=1)
        t_est = best_of(direct_fn, 1)
        pair_reps = min(41, max(repeats, 7, int(1.0 / max(t_est, 1e-3))))
        t_direct, t_facade, ratio = best_of_pair(direct_fn, facade_fn, pair_reps)
        d_bank = bank_store.partition_units(n, min_units=1)

        # Exact per-unit completion forced on the numpy bank (the lazy heap;
        # since the PR 5 routing fix this is also what "auto" runs on the
        # host path) plus the FORCED threshold column — the data behind
        # keeping host-auto on the heap: the threshold pass costs ~one extra
        # continuous solve here, a win only on the jitted backends.
        t_bank_exact = best_of(
            lambda: _partition_units_bank(
                bank, n, list(icaps), min_units=1, completion="greedy"
            ),
            ex_reps,
        )
        d_bank_exact, _ = _partition_units_bank(
            bank, n, list(icaps), min_units=1, completion="greedy"
        )
        t_bank_threshold = best_of(
            lambda: _partition_units_bank(
                bank, n, list(icaps), min_units=1, completion="threshold"
            ),
            ex_reps,
        )
        d_bank_threshold, _ = _partition_units_bank(
            bank, n, list(icaps), min_units=1, completion="threshold"
        )

        row = {
            "p": p,
            "n": n,
            "bank_s": t_direct,
            "bank_exact_s": t_bank_exact,
            "bank_threshold_s": t_bank_threshold,
            "facade_s": t_facade,
            "facade_overhead_pct": 100.0 * (ratio - 1.0),
            "completion_max_unit_diff": int(
                max(abs(a - b) for a, b in zip(d_bank_threshold, d_bank_exact))
            ),
            "completion_makespan_equal": makespan(d_bank_threshold)
            == makespan(d_bank_exact),
        }
        assert d_bank == d_bank_exact, "host auto must equal the greedy heap"
        if backend in ("numpy", "both") and p <= scalar_cutoff:
            scalar_store = SpeedStore.from_models(models, backend="scalar")
            t_scalar = best_of(
                lambda: scalar_store.partition_units(n, min_units=1), repeats
            )
            d_scalar = scalar_store.partition_units(n, min_units=1)
            row["scalar_s"] = t_scalar
            row["speedup"] = t_scalar / t_direct
            row["max_unit_diff"] = int(max(abs(a - b) for a, b in zip(d_scalar, d_bank)))
        if backend in ("jax", "both"):
            jbank = JaxModelBank.from_bank(bank)
            jax_store = SpeedStore.from_jax_bank(jbank)

            def jax_direct():
                return jbank.partition_units(n, icaps, min_units=1)

            def jax_facade():
                return jax_store.partition_units(n, min_units=1)

            def jax_exact():
                return jbank.partition_units(
                    n, icaps, min_units=1, completion="greedy"
                )

            t0 = time.perf_counter()
            jax_direct()  # traces + compiles for this fleet shape
            t_compile = time.perf_counter() - t0
            t_est = best_of(jax_direct, 1)  # post-compile
            jpair_reps = min(41, max(repeats, 7, int(1.0 / max(t_est, 1e-3))))
            t_jax, t_jax_facade, jratio = best_of_pair(
                jax_direct, jax_facade, jpair_reps
            )  # interleaved
            d_jax = jax_facade()
            jax_exact()  # compile the per-unit-completion variant
            t_jax_exact = best_of(jax_exact, ex_reps)  # steady-state
            d_jax_exact = jax_exact()
            row["jax_compile_s"] = t_compile
            row["jax_steady_s"] = t_jax
            row["jax_exact_s"] = t_jax_exact
            row["jax_completion_speedup"] = t_jax_exact / t_jax
            row["jax_facade_s"] = t_jax_facade
            row["jax_facade_overhead_pct"] = 100.0 * (jratio - 1.0)
            row["jax_vs_bank_speedup"] = t_direct / t_jax
            row["jax_max_unit_diff"] = int(
                max(abs(a - b) for a, b in zip(d_jax, d_bank))
            )
            row["jax_completion_max_unit_diff"] = int(
                max(abs(int(a) - int(b)) for a, b in zip(d_jax, d_jax_exact))
            )
            row["completion_makespan_equal"] = bool(
                row["completion_makespan_equal"]
                and makespan(np.asarray(d_jax)) == makespan(np.asarray(d_jax_exact))
            )
            if p in f32_ps:
                # Same pipeline in float32: the bank's dtype flows through
                # every jitted constant, so this is a true f32 run.
                jb32 = JaxModelBank(
                    xs=jnp.asarray(bank.xs, jnp.float32),
                    ss=jnp.asarray(bank.ss, jnp.float32),
                    counts=jnp.asarray(bank.counts),
                )
                d32 = jb32.partition_units(n, icaps, min_units=1)
                diffs = np.abs(np.asarray(d32) - np.asarray(d_bank))
                row["jax_f32_max_unit_diff"] = int(diffs.max())
                row["jax_f32_total_unit_drift"] = int(diffs.sum())
                row["jax_f32_drift_frac_of_n"] = float(diffs.sum() / n)
        if p >= 1000:
            # Two-level route over the same fleet: groups sized to keep each
            # inner block cache-resident.  Near-optimal (gated <= 1.12x flat
            # makespan), and the only route that scales past the flat bank's
            # memory wall — see the p=10^6 row in chip_smoke.py.
            gsize = 100 if p <= 1000 else 1000
            groups = (np.arange(p) // gsize).tolist()
            caps_np = np.asarray(icaps, dtype=np.int64)
            hn = Hierarchy.from_bank(bank, groups, backend="numpy")
            t_hier = best_of(
                lambda: hn.partition_units(n, caps_np, min_units=1), ex_reps
            )
            d_hier = hn.partition_units(n, caps_np, min_units=1)
            assert sum(d_hier) == n
            row["hier_group_size"] = gsize
            row["hier_s"] = t_hier
            row["hier_makespan_ratio"] = makespan(d_hier) / makespan(d_bank)
            if backend in ("jax", "both"):
                hj = Hierarchy.from_bank(bank, groups, backend="jax")

                def hier_jax():
                    return hj.partition_units(n, caps_np, min_units=1)

                t0 = time.perf_counter()
                d_hj = hier_jax()  # traces + compiles outer-agg + inner blocks
                row["hier_jax_compile_s"] = time.perf_counter() - t0
                row["hier_jax_s"] = best_of(hier_jax, ex_reps)
                assert sum(d_hj) == n
                row["hier_makespan_ratio"] = max(
                    row["hier_makespan_ratio"],
                    makespan(d_hj) / makespan(d_bank),
                )
        rows.append(row)
        msg = (
            f"p={p:6d}  bank={t_direct * 1e3:9.3f} ms"
            f" (exact {t_bank_exact * 1e3:9.3f} ms,"
            f" thr {t_bank_threshold * 1e3:9.3f} ms)"
            f"  facade=+{row['facade_overhead_pct']:5.2f}%"
        )
        if "scalar_s" in row:
            msg += (
                f"  scalar={row['scalar_s'] * 1e3:10.3f} ms"
                f"  speedup={row['speedup']:8.1f}x"
                f"  max|Δd|={row['max_unit_diff']}"
            )
        if "jax_steady_s" in row:
            msg += (
                f"  jax={row['jax_steady_s'] * 1e3:9.3f} ms"
                f" (compile {row['jax_compile_s']:6.2f} s,"
                f" facade +{row['jax_facade_overhead_pct']:.2f}%)"
                f"  jax_exact={row['jax_exact_s'] * 1e3:9.3f} ms"
                f" ({row['jax_completion_speedup']:6.1f}x)"
                f"  jax_max|Δd|={row['jax_max_unit_diff']}"
            )
        if "jax_f32_max_unit_diff" in row:
            msg += (
                f"  f32|Δd|max={row['jax_f32_max_unit_diff']}"
                f" Σ={row['jax_f32_total_unit_drift']}"
            )
        if "hier_s" in row:
            msg += (
                f"  hier={row['hier_s'] * 1e3:9.3f} ms"
            )
            if "hier_jax_s" in row:
                msg += (
                    f"  hier_jax={row['hier_jax_s'] * 1e3:9.3f} ms"
                    f" (compile {row['hier_jax_compile_s']:6.2f} s)"
                )
            msg += f"  makespan x{row['hier_makespan_ratio']:.4f}"
        print(msg, flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="small sweep for CI smoke")
    ap.add_argument("--backend", choices=["numpy", "jax", "both"], default="both")
    ap.add_argument("--out", default="BENCH_partition.json")
    ap.add_argument("--repeats", type=int, default=None)
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.quick:
        # p=1000 is included so the p==1000 acceptance gates (facade tax,
        # jax-vs-bank steady state, completion-mode divergence) actually run
        # in the CI smoke, not just in full sweeps.  The scalar column is
        # skipped above p=100 to keep the smoke fast; the gates don't need it.
        ps, repeats, cutoff = [10, 100, 1000], args.repeats or 2, 100
        f32_ps = ()  # drift quantification is a full-sweep question
    else:
        # p=10^5 is the threshold-count completion's target scale (the
        # >=10x fast-vs-per-unit gate below); the seed scalar path stops at
        # p=10^4 (it already takes ~2 minutes per call there).  Float32
        # drift is measured at BOTH serving scales — the dtype-policy docs
        # in speedstore.py cite the pair.
        ps, repeats, cutoff = [10, 100, 1000, 10000, 100000], args.repeats or 3, 10**4
        f32_ps = (10**4, 10**5)

    rows = run_sweep(ps, repeats, args.backend, scalar_cutoff=cutoff, f32_ps=f32_ps)

    payload = {
        "benchmark": "partition_scale",
        "description": (
            "partition_units latency via the SpeedStore/Scheduler facade: "
            "seed scalar path vs numpy ModelBank vs jitted JaxModelBank "
            "(x64; steady-state = post-compile; facade_* columns measure the "
            "facade's validation+dispatch tax over the raw kernels; "
            "*_exact_s columns force the per-unit greedy completion vs the "
            "default threshold-count completion on these monotone fleets, "
            "with jax_completion_speedup the fast-vs-per-unit ratio gated "
            ">=10x at p=10^5; jax_f32_* columns quantify float32 drift at "
            "p=10^4 and p=10^5; hier_* columns time the two-level Hierarchy "
            "route at p>=1000 with its makespan gated <= 1.12x flat)"
        ),
        "units_per_proc": 100,
        "repeats": repeats,
        "backend": args.backend,
        "sweep": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"-> {args.out}")

    rc = 0
    checked = [r for r in rows if "speedup" in r]
    big = [r for r in checked if r["p"] >= 1000]
    if big and min(r["speedup"] for r in big) < 10.0:
        print("WARNING: <10x speedup at p>=1000")
        rc = 1
    if any(r["max_unit_diff"] > 1 for r in checked):
        print("WARNING: scalar/bank paths disagree by >1 unit")
        rc = 1
    # Facade tax gate at the paper-scale fleet (p=1000, the same anchor as
    # the jax-vs-bank gate below): the unified API must cost <= 5% over the
    # raw kernel.  Other p are latency-noise dominated on shared runners
    # (the real tax is an O(p) validation pass, ~60us at p=1000) and are
    # reported informationally.
    over = [r for r in rows if r["p"] == 1000 and r["facade_overhead_pct"] > 5.0]
    if over:
        print("FAIL: facade overhead > 5% at p=1000:",
              [(r["p"], round(r["facade_overhead_pct"], 2)) for r in over])
        rc = 1
    for r in rows:
        if r["p"] > 1000 and r["facade_overhead_pct"] > 5.0:
            print(f"note: facade overhead {r['facade_overhead_pct']:.2f}% at "
                  f"p={r['p']} (informational; shared-runner noise floor)")
    jaxed = [r for r in rows if "jax_max_unit_diff" in r]
    if jaxed:
        import jax

        if jax.default_backend() == "cpu":
            # Bit-identity is a CPU contract (same FPU, same reduction
            # order); on accelerators a 1-ulp sum difference may move one
            # boundary unit, so there only >1-unit drift is a failure.
            if any(r["jax_max_unit_diff"] != 0 for r in jaxed):
                print("FAIL: jax allocations not bit-identical to the numpy bank")
                rc = 1
        elif any(r["jax_max_unit_diff"] > 1 for r in jaxed):
            print("FAIL: jax allocations differ from the numpy bank by >1 unit")
            rc = 1
    # Hard gate at the paper-scale fleet (p=1000): steady-state jitted
    # repartition must not lose to the numpy bank.  Larger p is reported but
    # informational.
    slow = [r for r in jaxed if r["p"] == 1000 and r["jax_steady_s"] > r["bank_s"]]
    if slow:
        print("FAIL: jax steady-state slower than numpy bank at p=1000")
        rc = 1
    for r in jaxed:
        if r["p"] > 1000 and r["jax_steady_s"] > r["bank_s"]:
            print(f"note: jax steady-state behind numpy bank at p={r['p']} "
                  f"({r['jax_steady_s']*1e3:.0f} ms vs {r['bank_s']*1e3:.0f} ms)")
    # Completion-mode divergence gate: the threshold-count fast path (what
    # "auto" picks on these monotone fleets) must hit the SAME makespan as
    # the exact per-unit completion.  Enforced at p=1000 (runs in the CI
    # smoke); other p are reported.
    div = [r for r in rows if not r.get("completion_makespan_equal", True)]
    if any(r["p"] == 1000 for r in div):
        print("FAIL: threshold-count completion diverges from the per-unit "
              "completion makespan at p=1000")
        rc = 1
    for r in div:
        if r["p"] != 1000:
            print(f"note: completion-mode makespan divergence at p={r['p']}")
    # The tentpole acceptance gate: at p=10^5 the threshold-count completion
    # must beat the sequential per-unit jax completion by >= 10x steady-state
    # (full sweeps only — quick mode stops at p=1000).
    big_jax = [r for r in jaxed if r["p"] >= 10**5]
    if big_jax and min(r["jax_completion_speedup"] for r in big_jax) < 10.0:
        print("FAIL: threshold-count completion < 10x over the per-unit jax "
              "completion at p=10^5")
        rc = 1
    # Hierarchical near-optimality gate: the two-level makespan must stay
    # within the fuzz-test envelope of the flat optimum at every swept p.
    bad_hier = [r for r in rows if r.get("hier_makespan_ratio", 1.0) > 1.12]
    if bad_hier:
        print("FAIL: hierarchical makespan > 1.12x flat:",
              [(r["p"], round(r["hier_makespan_ratio"], 4)) for r in bad_hier])
        rc = 1
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
