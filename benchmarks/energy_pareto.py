"""Energy/makespan Pareto fronts + power-capped serving replay.

Two parts, one payload (``BENCH_energy.json``):

**Part A — front construction cost vs p.**  For each fleet size p, a
heterogeneous plateau/knee speed fixture plus affine per-replica energy
laws ``E_i(x) = a_i + b_i x`` (banked as energy-rate FPMs, see
``core/energy.py``) and the full makespan/energy Pareto front is built on
the numpy and jax backends — the jax route batches all interior
time-threshold bisections into ONE stacked ``[T, p, k]`` program.
Reported: post-compile median front wall per backend.  Gated (exit 1), per
row and backend:

  * the front is strictly monotone (times increasing, energies
    decreasing — no dominated points survive construction);
  * the endpoints equal the pure single-objective partitions
    (``objective="time"`` / ``objective="energy"``) exactly;
  * numpy and jax produce bit-identical fronts (times, energies, and
    every allocation row — zero divergence).

**Part B — the PR 7 serving trace under a stepped power cap.**  The
serve_trace harness's seeded arrival trace (Poisson x diurnal x flash,
tenant admit/retire, drifting replica speeds, one runaway straggler) is
replayed through three arms serving the IDENTICAL epochs:

  * **uncapped** — the adaptive serving loop (warm-admitted tenants,
    ``rebalance(loads)`` + ``observe`` folds every epoch), no energy cap;
    its per-epoch model-priced energy defines the budget baseline;
  * **capped** — the same loop with ``FleetScheduler.power_cap`` set to
    0.97x a STEPPED budget (1.05 / 0.70 / 0.85 of the uncapped arm's
    per-epoch energy across the three thirds of the trace): when the cap
    binds, ``_apply_power_cap`` walks all tenants up a common
    makespan-stretch factor along their Pareto fronts until the fleet
    fits;
  * **throttle** — the naive DVFS baseline: keep the uncapped
    allocations' SHAPE and scale every replica's frequency by one global
    phi (busy times x 1/phi, dynamic energy per chunk x phi — frequency
    scaling at fixed voltage), with phi chosen per epoch so the fleet
    fits the same budget.

Energy ground truth IS the banked rate model (the same pricing the cap
enforces), with per-replica efficiency deliberately NOT aligned with
speed: the first replica of each device class is an older, power-hungrier
part at the same speed, so a binding cap has somewhere cheap to move work
— the regime the Pareto allocator exists for.  A uniform throttle slows
the efficient replicas exactly as much as the hogs; the capped arm
reroutes instead.  Gated (exit 1):

  * the capped arm's model-priced fleet energy fits the budget EVERY
    epoch (binding or not — the 3% cap margin absorbs pricing noise);
  * the capped arm beats the uniform-throttle baseline on whole-trace
    p99 latency.

    PYTHONPATH=src python benchmarks/energy_pareto.py [--quick] [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax

from repro.compile_cache import use_compile_cache
from repro.core import PiecewiseLinearFPM, SpeedStore
from repro.core.energy import energy_model
from repro.fleet import FleetScheduler, JobSpec
from repro.runtime.straggler import StragglerAction

from serve_trace import (
    QUICK,
    FULL,
    ArmStats,
    TraceConfig,
    World,
    active_rids,
    build_trace,
    build_world,
    slo_seconds,
    world_with_joiner,
)

RESERVE_KNOTS = 64  # fixed [q, p, k] carry shapes (the serve_trace setting)
QUANTIZE = 0.05  # fold-grid pitch: bounded knot sets under observe folds
CAP_MARGIN = 0.97  # power_cap = margin * budget: pricing-noise headroom
PHI_MIN = 0.05  # throttle floor: below this the baseline just overspends
BUDGET_STEPS = (1.05, 0.70, 0.85)  # budget/uncapped-energy per trace third


# ---------------------------------------------------------------------------
# Part A: front construction cost vs p
# ---------------------------------------------------------------------------


def front_fixture(p: int, seed: int):
    """Heterogeneous plateau/knee speed models + affine energy laws with
    per-replica (a, b) spread — efficiency uncorrelated with speed."""
    rng = np.random.default_rng(seed)
    base = rng.uniform(1e-6, 3e-6, p)
    knee = rng.uniform(2e3, 2e4, p)
    ea = rng.uniform(1.0, 50.0, p)
    eb = rng.uniform(0.05, 2.0, p)
    n = 100 * p
    speed, energy = [], []
    for i in range(p):
        xs = np.geomspace(16.0, 8.0 * knee[i], 6)
        ts = xs * base[i] * (
            1.0 + np.where(xs > knee[i], 3.0 * (xs - knee[i]) / knee[i], 0.0)
        )
        speed.append(PiecewiseLinearFPM.from_points(list(zip(xs, xs / ts))))
        exs = np.geomspace(1.0, 4.0 * n, 7)
        energy.append(energy_model(list(zip(exs, ea[i] + eb[i] * exs))))
    return speed, energy, n


def front_row(p: int, *, reps: int, num_points: int, seed: int) -> dict:
    """Build the front on numpy and jax, time it post-compile, and run the
    three correctness gates on the pair."""
    speed, energy, n = front_fixture(p, seed)
    stores = {
        b: SpeedStore.from_models(
            [PiecewiseLinearFPM.from_points(m.as_points()) for m in speed],
            backend=b,
        ).attach_energy(
            [PiecewiseLinearFPM.from_points(m.as_points()) for m in energy]
        )
        for b in ("numpy", "jax")
    }

    fronts, walls = {}, {}
    for b, store in stores.items():
        fronts[b] = store.pareto_front(n, num_points=num_points)  # warm/compile
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            store.pareto_front(n, num_points=num_points)
            times.append(time.perf_counter() - t0)
        walls[b] = float(np.median(times) * 1e3)

    ok = True
    for b, fr in fronts.items():
        if not (np.all(np.diff(fr.times) > 0) and np.all(np.diff(fr.energies) < 0)):
            print(f"FRONT FAIL: non-monotone front on {b} at p={p}")
            ok = False
        d_time = stores[b].partition_units(n)
        d_energy = stores[b].partition_units(n, objective="energy")
        if list(fr.allocations[0]) != d_time:
            print(f"FRONT FAIL: time endpoint != objective='time' solve "
                  f"on {b} at p={p}")
            ok = False
        if list(fr.allocations[-1]) != d_energy:
            print(f"FRONT FAIL: energy endpoint != objective='energy' solve "
                  f"on {b} at p={p}")
            ok = False
    fn, fj = fronts["numpy"], fronts["jax"]
    diverged = (
        len(fn) != len(fj)
        or not np.array_equal(fn.times, fj.times)
        or not np.array_equal(fn.energies, fj.energies)
        or not np.array_equal(fn.allocations, fj.allocations)
    )
    if diverged:
        print(f"FRONT FAIL: numpy/jax fronts diverge at p={p}")
        ok = False

    return {
        "p": p,
        "n": n,
        "num_points": num_points,
        "front_points": len(fn),
        "front_ms_numpy": walls["numpy"],
        "front_ms_jax": walls["jax"],
        "monotone_and_endpoints_ok": ok and not diverged,
        "numpy_jax_divergence_rows": int(diverged),
        "ok": ok,
    }


# ---------------------------------------------------------------------------
# Part B: the serving trace under a stepped power cap
# ---------------------------------------------------------------------------


def fleet_layout(cfg: TraceConfig) -> List[Tuple[str, float]]:
    """(class, deploy speed) per replica SLOT, including the scripted
    joiner (full mode): the fleet is built at full width and inactive
    slots — the joiner before its join epoch, leavers/quarantines after —
    are masked with per-job caps of 0, so membership changes never restack
    or resize the scheduler."""
    entries = [(c, s) for c, s in cfg.replicas]
    if cfg.join is not None:
        entries.append((cfg.join[0], cfg.join[1]))
    return entries


def energy_coeffs(cfg: TraceConfig) -> List[Tuple[float, float]]:
    """Per-replica affine energy law ``E_i(x) = a_i + b_i x`` (per tenant
    slice of x chunks).  Generation skew: the FIRST replica of each device
    class is an older part — same speed, 6x the dynamic power — so the
    efficiency ranking is deliberately not the speed ranking."""
    base = {"fast": (6.0, 0.20), "mid": (3.0, 0.25), "slow": (2.0, 0.15)}
    seen: Dict[str, int] = {}
    out = []
    for cls, _speed in fleet_layout(cfg):
        a, b = base[cls]
        if seen.get(cls, 0) == 0 and cls == "fast":
            a, b = 2.0 * a, 6.0 * b
        seen[cls] = seen.get(cls, 0) + 1
        out.append((a, b))
    return out


def build_energy_models(cfg: TraceConfig) -> List[PiecewiseLinearFPM]:
    coeffs = energy_coeffs(cfg)
    xs = np.geomspace(1.0, 16384.0, 9)
    return [
        energy_model(list(zip(xs, a + b * xs))) for a, b in coeffs
    ]


def slice_energy(emodels, d) -> float:
    """Model-priced energy of ONE tenant slice: ``sum_i E_i(d_i)`` over
    replicas with units — the exact pricing ``_apply_power_cap`` uses."""
    return float(sum(
        emodels[i].time(float(di)) for i, di in enumerate(d) if di > 0
    ))


def budget_schedule(cfg: TraceConfig, uncapped_energy: List[float]) -> List[float]:
    """The stepped budget: each third of the trace gets a fixed fraction of
    the uncapped arm's per-epoch energy (the middle third binds hard)."""
    out = []
    for e in range(cfg.epochs):
        frac = BUDGET_STEPS[min(3 * e // cfg.epochs, 2)]
        out.append(frac * uncapped_energy[e])
    return out


def run_serving_arm(
    cfg: TraceConfig,
    world: World,
    trace,
    *,
    budgets: Optional[List[float]] = None,
):
    """The adaptive serving loop (warm admit, rebalance + straggler scan +
    observe folds — the PR 7 serving arm minus the session churn),
    optionally power-capped to 0.97x the per-epoch budget.  A QUARANTINE
    on a replica — and the scripted join/leave slots — are enforced as
    per-job caps of 0, so a dying replica stops being allocatable whether
    the allocator wants it for speed OR for efficiency (the energy solver
    otherwise fills an efficient straggler to its threshold cap while its
    speed estimate lags the decay).  Returns the latency summary plus
    per-epoch allocation/busy/energy records (the throttle baseline is
    derived from the uncapped arm's records)."""
    entries = fleet_layout(cfg)
    p = len(entries)
    rids = list(range(p))
    emodels = build_energy_models(cfg)
    deploy_epoch = [
        cfg.join[2] if cfg.join is not None and r == len(cfg.replicas) else 0
        for r in rids
    ]
    warm_speed = [
        PiecewiseLinearFPM.from_points(
            [(1.0, world.speed(r, deploy_epoch[r])),
             (16384.0, world.speed(r, deploy_epoch[r]))]
        )
        for r in rids
    ]
    fleet = FleetScheduler(
        p, backend="jax", reserve_knots=RESERVE_KNOTS, quantize=QUANTIZE,
    )
    stats = ArmStats(slo_s=slo_seconds(cfg), drift_window=cfg.drift_step[1:3])
    noise_rng = np.random.default_rng(cfg.seed + 1)
    sched_host = 0.0
    quarantined: set = set()
    energy_trace: List[float] = []
    records: List[Dict[str, object]] = []
    BIG = 10**6
    cur_caps: Optional[List[int]] = None

    for e in range(cfg.epochs):
        active = set(active_rids(cfg, e, quarantined))
        caps = [BIG if r in active else 0 for r in rids]
        if cur_caps is not None and caps != cur_caps:
            for name in list(fleet.active_jobs):
                fleet.resize(name, caps=caps)
        cur_caps = caps

        tenants = {name: int(n) for name, n in trace[e].items()}
        for name in list(fleet.active_jobs):
            if name not in tenants:
                fleet.retire(name, save_profile=False)
        for name, n in tenants.items():
            if name not in fleet.active_jobs:
                fleet.admit(
                    JobSpec(name=name, n=n, eps=0.05, min_units=0, caps=caps),
                    models=warm_speed,
                    energy_models=emodels,
                )
        if budgets is not None:
            fleet.power_cap = CAP_MARGIN * budgets[e]

        t0 = time.perf_counter()
        ds = fleet.rebalance(tenants)
        sched_host += time.perf_counter() - t0

        true = world.speeds(rids, e)
        counts = np.zeros(p, dtype=np.int64)
        busy = np.zeros(p, dtype=np.float64)
        times: Dict[str, List[float]] = {}
        epoch_energy = 0.0
        for name, d in ds.items():
            d = np.asarray(d, dtype=np.int64)
            t = np.where(d > 0, d / true, 0.0)
            t *= 1.0 + 0.02 * noise_rng.standard_normal(p)
            t = np.where(d > 0, np.maximum(t, 1e-12), 0.0)
            times[name] = [float(v) for v in t]
            counts += d
            busy += t
            epoch_energy += slice_energy(emodels, d)
        stats.record(e, counts, busy)
        energy_trace.append(epoch_energy)
        records.append({"ds": {k: list(map(int, v)) for k, v in ds.items()},
                        "busy": busy.copy()})

        t0 = time.perf_counter()
        acts = fleet.straggler_actions(times)  # pre-fold predictions
        fleet.observe(times)
        sched_host += time.perf_counter() - t0
        for i, act in enumerate(acts):
            if act is StragglerAction.QUARANTINE:
                quarantined.add(i)  # caps drop to 0 from the next epoch

    out = stats.summary()
    out["sched_host_s"] = sched_host
    out["energy_total"] = float(np.sum(energy_trace))
    out["quarantined_replicas"] = sorted(int(r) for r in quarantined)
    return out, energy_trace, records


def run_throttle_arm(cfg: TraceConfig, records, budgets: List[float]):
    """The naive uniform-throttle baseline: per epoch, keep the uncapped
    allocations and pick ONE global frequency scale phi so the fleet fits
    the budget — every busy time x 1/phi, every slice's dynamic energy
    x phi (frequency scaling at fixed voltage; the static ``a_i`` term is
    spent regardless)."""
    coeffs = energy_coeffs(cfg)
    emodels = build_energy_models(cfg)
    stats = ArmStats(slo_s=slo_seconds(cfg), drift_window=cfg.drift_step[1:3])
    energy_trace: List[float] = []
    phis: List[float] = []
    for e, rec in enumerate(records):
        static = dyn = 0.0
        counts = np.zeros(len(fleet_layout(cfg)), dtype=np.int64)
        for d in rec["ds"].values():
            counts += np.asarray(d, dtype=np.int64)
            for i, di in enumerate(d):
                if di > 0:
                    static += coeffs[i][0]
                    dyn += emodels[i].time(float(di)) - coeffs[i][0]
        if static + dyn <= budgets[e]:
            phi = 1.0
        elif static >= budgets[e]:
            phi = PHI_MIN  # can't fit even at the floor: overspends
        else:
            phi = max(PHI_MIN, min(1.0, (budgets[e] - static) / dyn))
        phis.append(phi)
        stats.record(e, counts, np.asarray(rec["busy"]) / phi)
        energy_trace.append(static + phi * dyn)
    out = stats.summary()
    out["phi_min_applied"] = float(min(phis))
    out["epochs_throttled"] = int(sum(1 for v in phis if v < 1.0))
    return out, energy_trace


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: small p sweep + the QUICK trace")
    ap.add_argument("--out", default="BENCH_energy.json")
    args = ap.parse_args(argv)
    use_compile_cache()

    # benchmark-process only (the test suite imports serve_trace; flipping
    # x64 at import time would change every other test)
    jax.config.update("jax_enable_x64", True)

    # --- Part A: front construction cost + correctness gates ---------------
    if args.quick:
        ps, reps, num_points = [8, 64], 3, 17
    else:
        ps, reps, num_points = [8, 64, 256, 1024], 5, 33
    front_rows = []
    fronts_ok = True
    for i, p in enumerate(ps):
        row = front_row(p, reps=reps, num_points=num_points, seed=100 + i)
        front_rows.append(row)
        fronts_ok = fronts_ok and row["ok"]
        print(f"front p={p:5d} ({row['front_points']:3d} pts): "
              f"numpy {row['front_ms_numpy']:8.2f} ms  "
              f"jax {row['front_ms_jax']:8.2f} ms  "
              f"{'OK' if row['ok'] else 'FAIL'}", flush=True)

    # --- Part B: capped serving replay -------------------------------------
    cfg = QUICK if args.quick else FULL
    world = world_with_joiner(cfg, build_world(cfg))
    trace = build_trace(cfg)
    print(f"trace: {cfg.epochs} epochs x {cfg.dt}s, "
          f"{len(cfg.replicas)} replicas, seed={cfg.seed}, "
          f"budget steps {BUDGET_STEPS}", flush=True)

    uncapped, e_unc, records = run_serving_arm(cfg, world, trace)
    budgets = budget_schedule(cfg, e_unc)
    capped, e_cap, _ = run_serving_arm(cfg, world, trace, budgets=budgets)
    throttle, e_thr = run_throttle_arm(cfg, records, budgets)

    for name, row in (("uncapped", uncapped), ("capped", capped),
                      ("throttle", throttle)):
        print(f"{name:9s} p50 {row['latency_p50_s']:.3f}s "
              f"p99 {row['latency_p99_s']:.3f}s "
              f"goodput {row['goodput']:.3f}", flush=True)
    print(f"energy: uncapped {sum(e_unc):.0f}  budget {sum(budgets):.0f}  "
          f"capped {sum(e_cap):.0f}  throttle {sum(e_thr):.0f}", flush=True)

    over = [e for e in range(cfg.epochs) if e_cap[e] > budgets[e] * (1 + 1e-9)]
    binding = [e for e in range(cfg.epochs)
               if CAP_MARGIN * budgets[e] < e_unc[e]]
    print(f"cap binds on {len(binding)}/{cfg.epochs} epochs; "
          f"capped arm over budget on {len(over)}", flush=True)

    rc = 0
    if not fronts_ok:
        print("FAIL: Pareto front gates (monotonicity / endpoints / "
              "numpy-jax parity)")
        rc = 1
    if not binding:
        print("FAIL: the stepped budget never binds — the replay is vacuous")
        rc = 1
    if over:
        print(f"FAIL: capped serving exceeded the budget on epochs {over[:8]}")
        rc = 1
    if capped["latency_p99_s"] >= throttle["latency_p99_s"]:
        print(f"FAIL: capped p99 {capped['latency_p99_s']:.3f}s >= "
              f"uniform-throttle p99 {throttle['latency_p99_s']:.3f}s")
        rc = 1
    if rc == 0:
        print("all gates OK")

    payload = {
        "benchmark": "energy_pareto",
        "description": (
            "bi-objective time/energy subsystem: (A) makespan/energy "
            "Pareto front construction vs fleet size, numpy vs jax (all "
            "interior time-threshold bisections batched into one stacked "
            "[T, p, k] program), gated on strict monotonicity, "
            "endpoint-equals-pure-objective parity, and zero numpy/jax "
            "divergence; (B) the serve_trace arrival trace replayed under "
            "a stepped per-epoch energy budget: adaptive capped serving "
            "(FleetScheduler.power_cap walks tenants up a common "
            "makespan-stretch factor along their Pareto fronts) vs "
            "uncapped vs a naive uniform DVFS throttle (one global "
            "frequency scale, busy x 1/phi, dynamic energy x phi); "
            "energy ground truth is the banked rate model with "
            "generation-skewed efficiency (first fast replica = older, "
            "6x dynamic power), gated on within-budget-every-epoch and "
            "capped-beats-throttle p99"
        ),
        "mode": "quick" if args.quick else "full",
        "front_sweep": front_rows,
        "fronts_ok": fronts_ok,
        "serving": {
            "config": {
                "epochs": cfg.epochs, "dt_s": cfg.dt, "seed": cfg.seed,
                "replicas": [
                    {"rid": i, "class": c, "base_speed": s,
                     "energy_a": energy_coeffs(cfg)[i][0],
                     "energy_b": energy_coeffs(cfg)[i][1]}
                    for i, (c, s) in enumerate(cfg.replicas)
                ],
                "budget_steps": list(BUDGET_STEPS),
                "cap_margin": CAP_MARGIN,
                "slo_s": slo_seconds(cfg),
            },
            "arms": {"uncapped": uncapped, "capped": capped,
                     "throttle": throttle},
            "energy_per_epoch": {
                "budget": [float(v) for v in budgets],
                "uncapped": [float(v) for v in e_unc],
                "capped": [float(v) for v in e_cap],
                "throttle": [float(v) for v in e_thr],
            },
            "binding_epochs": len(binding),
            "over_budget_epochs": over,
        },
        "gates_ok": rc == 0,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"-> {args.out}")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
