"""Traffic-trace serving harness: adaptive DFPA fleet vs static vs oracle.

The paper's headline claim — the cost of the optimal distribution is orders
of magnitude below the execution it optimizes — restated at serving
timescales: a synthetic request-arrival trace (Poisson base rate + diurnal
modulation + a flash-crowd segment, seeded/deterministic) drives
``ReplicaDispatcher.balance_fleet`` over a simulated heterogeneous replica
fleet with drifting speed functions, one injected runaway straggler
(throttled mid-trace; REPROFILE→QUARANTINE must fire on the RIGHT replica),
mid-trace tenant admit/retire, and (full mode) replica join/leave.

Three arms serve the IDENTICAL trace:

  * **adaptive** — the repo's serving loop: ``balance_fleet`` warm sessions
    at membership changes (one measured round each), and per steady epoch
    ``fleet.rebalance(loads)`` → simulate → ``fleet.straggler_actions`` →
    ``fleet.observe`` (scan BEFORE fold: strike predictions come from the
    pre-epoch estimates).  A QUARANTINE removes the replica (fresh session,
    profiles carried via the registry, detector remapped through the
    survivors).
  * **static** — each replica's share fixed proportional to its DEPLOY-TIME
    speed (measured once, never updated): correct at t=0, wrong under
    drift, catastrophic under the runaway straggler it can't drop.
  * **oracle** — proportional to the TRUE drifted speeds every epoch (the
    unachievable lower bound: no measurement, no lag).

Serving model: per epoch, each replica serves its tenants' slices back to
back (time-sliced — ``FleetRoundLog``'s accounting), so replica ``i``'s
busy time is the SUM across tenants of ``d_k[i] / speed_i(t)`` and the
``j``-th of its ``c_i`` chunks completes at ``busy_i * j / c_i``.  Reported
per arm: p50/p99 request latency, goodput (fraction of chunks inside the
SLO), drift-segment goodput, mean epoch wall.  Adaptive also reports
rebalance reaction times (trace time from the drift / straggler onset to
the first visible response) and rebalance overhead — scheduler host seconds
(balance_fleet walls minus time spent inside ``replica_run``, plus
rebalance/scan/observe walls) as a fraction of total SIMULATED serving
seconds.

Acceptance gates (exit 1):
  (a) adaptive goodput >= static goodput on the drifting-speed segment, and
      adaptive p99 latency < static p99 over the whole trace;
  (b) straggler reaction: REPROFILE fires on the throttled replica within
      ``REACTION_BOUND_EPOCHS`` epochs of onset, QUARANTINE fires on that
      same replica and on no other; a REPROFILE on a healthy replica counts
      as a misfire unless it lands within ``REACTION_BOUND_EPOCHS`` epochs
      of a fresh-from-registry session (there the detector is EXPECTED to
      clear stale merged class profiles — reported as ``grace_reprofiles``);
  (c) warm-session no-recompile: a repeated ``balance_fleet`` call reuses
      ``self.fleet`` (identity), performs zero restacks and zero new jit
      compilations (``_cache_size`` deltas on the stacked partition and
      fold-in programs);
  (d) rebalance overhead <= 1% of total trace serving time.

Results are written to ``BENCH_serve.json``.

``--trace out.json`` additionally runs the adaptive arm under a
``repro.obs.FlightRecorder`` and writes a Chrome-trace/Perfetto JSON of the
whole serving session (fleet rounds, rebalance/observe spans, straggler
strike/verdict events, the canonical ``serve.rebalance_overhead_frac``
gauge).  On a QUARANTINE verdict — or on any gate failure — the recorder
dumps ``out.json.flightrec.json`` naming the incident replica and the
strike evidence that convicted it.  The written trace is validated (parses,
>= 1 fleet span per epoch, overhead gauge == the harness fraction); a
validation failure exits 1 like any gate.

    PYTHONPATH=src python benchmarks/serve_trace.py [--quick] [--out FILE]
        [--trace TRACE]
"""

from __future__ import annotations

import argparse
import json
import math
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax

from repro.compile_cache import use_compile_cache
from repro.core.executor import FleetRoundLog
from repro.fleet import ProfileRegistry
from repro.runtime.serve_loop import ReplicaDispatcher
from repro.runtime.straggler import StragglerAction

REACTION_BOUND_EPOCHS = 6  # gate (b): REPROFILE within this many epochs
OVERHEAD_BOUND = 0.01  # gate (d): scheduler host s / simulated serving s
RESERVE_KNOTS = 64  # fixed [q, p, k] carry shapes -> precompilable
QUANTIZE = 0.05  # fold-grid pitch (all folds): bounded knot set per replica
STALENESS_TOL = 0.5  # drop a registry class profile this far off on round 1


# ---------------------------------------------------------------------------
# world: heterogeneous replicas with drifting speed functions
# ---------------------------------------------------------------------------


@dataclass
class Replica:
    rid: int
    cls: str
    base: float  # chunks/second at amplitude midpoint
    phase: float  # drift sinusoid phase


@dataclass
class World:
    """Ground truth the arms are measured against.  Speeds drift as
    per-replica sinusoids; one replica takes a step drift (the gate
    segment) and one a runaway decay (the straggler)."""

    replicas: List[Replica]
    drift_amp: float
    drift_period: float  # epochs
    drift_step: Tuple[int, int, int, float]  # rid, start, end, multiplier
    straggler: Tuple[int, int, float, float]  # rid, onset, decay/epoch, floor

    def speed(self, rid: int, epoch: int) -> float:
        r = next(rep for rep in self.replicas if rep.rid == rid)
        s = r.base * (
            1.0
            + self.drift_amp
            * math.sin(2.0 * math.pi * epoch / self.drift_period + r.phase)
        )
        sr, s0, s1, mult = self.drift_step
        if rid == sr and s0 <= epoch < s1:
            s *= mult
        gr, onset, decay, floor = self.straggler
        if rid == gr and epoch >= onset:
            s *= max(decay ** (epoch - onset + 1), floor)
        return max(s, 1e-9)

    def speeds(self, rids: Sequence[int], epoch: int) -> np.ndarray:
        return np.asarray([self.speed(r, epoch) for r in rids], dtype=np.float64)


# ---------------------------------------------------------------------------
# trace: seeded arrivals + scripted membership events
# ---------------------------------------------------------------------------


@dataclass
class TraceConfig:
    epochs: int
    dt: float  # seconds of trace time per epoch
    seed: int
    replicas: List[Tuple[str, float]]  # (device class, base speed)
    drift_amp: float
    drift_period: float
    drift_step: Tuple[int, int, int, float]
    straggler: Tuple[int, int, float, float]
    tenants: Dict[str, float]  # name -> mean arrivals/epoch
    diurnal_amp: float
    diurnal_period: float  # epochs
    flash: Tuple[str, int, int, float]  # tenant, start, end, multiplier
    admit: Optional[Tuple[str, float, int, int]]  # name, rate, at, retire_at
    join: Optional[Tuple[str, float, int]] = None  # class, speed, at epoch
    leave: Optional[Tuple[int, int]] = None  # rid, at epoch
    slo_factor: float = 1.4  # SLO = factor * mean-load epoch wall at t=0


QUICK = TraceConfig(
    epochs=60,
    dt=2.0,
    seed=7,
    replicas=[("fast", 800.0), ("fast", 780.0), ("mid", 400.0),
              ("mid", 390.0), ("slow", 200.0)],
    drift_amp=0.2,
    drift_period=50.0,
    drift_step=(0, 12, 32, 0.55),
    straggler=(3, 46, 0.55, 0.05),
    tenants={"chat": 1500.0, "embed": 600.0},
    diurnal_amp=0.3,
    diurnal_period=40.0,
    flash=("chat", 36, 44, 2.5),
    admit=("burst", 300.0, 18, 30),
)

FULL = TraceConfig(
    epochs=240,
    dt=2.0,
    seed=17,
    replicas=[("fast", 800.0), ("fast", 780.0), ("mid", 400.0),
              ("mid", 390.0), ("slow", 200.0), ("slow", 195.0)],
    drift_amp=0.25,
    drift_period=100.0,
    drift_step=(1, 40, 80, 0.55),
    straggler=(3, 120, 0.55, 0.05),
    tenants={"chat": 1500.0, "embed": 600.0},
    diurnal_amp=0.35,
    diurnal_period=96.0,
    flash=("chat", 90, 110, 2.5),
    admit=("burst", 350.0, 60, 140),
    join=("mid", 410.0, 160),
    leave=(5, 200),
)


def build_world(cfg: TraceConfig) -> World:
    reps = [
        Replica(rid=i, cls=c, base=s, phase=0.61803 * (i + 1) * 2.0 * math.pi)
        for i, (c, s) in enumerate(cfg.replicas)
    ]
    return World(
        replicas=reps,
        drift_amp=cfg.drift_amp,
        drift_period=cfg.drift_period,
        drift_step=cfg.drift_step,
        straggler=cfg.straggler,
    )


def build_trace(cfg: TraceConfig) -> List[Dict[str, int]]:
    """Per-epoch per-tenant arrival counts — Poisson base rate x diurnal
    modulation x flash-crowd multiplier, fully determined by ``cfg.seed``."""
    rng = np.random.default_rng(cfg.seed)
    sched: List[Dict[str, int]] = []
    names = list(cfg.tenants)
    if cfg.admit is not None:
        names.append(cfg.admit[0])
    pmax = len(cfg.replicas) + 2
    for e in range(cfg.epochs):
        row: Dict[str, int] = {}
        for j, name in enumerate(names):
            if cfg.admit is not None and name == cfg.admit[0]:
                if not (cfg.admit[2] <= e < cfg.admit[3]):
                    continue
                rate = cfg.admit[1]
            else:
                rate = cfg.tenants[name]
            rate *= 1.0 + cfg.diurnal_amp * math.sin(
                2.0 * math.pi * e / cfg.diurnal_period + 1.7 * j
            )
            fname, f0, f1, fmult = cfg.flash
            if name == fname and f0 <= e < f1:
                rate *= fmult
            row[name] = max(int(rng.poisson(max(rate, 1.0))), pmax)
        sched.append(row)
    return sched


# ---------------------------------------------------------------------------
# serving-model helpers
# ---------------------------------------------------------------------------


def prop_split(n: int, w: np.ndarray) -> np.ndarray:
    """Largest-remainder integer split of ``n`` proportional to ``w``."""
    f = n * w / w.sum()
    d = np.floor(f).astype(np.int64)
    rem = int(n - d.sum())
    if rem > 0:
        order = np.argsort(-(f - d))
        d[order[:rem]] += 1
    return d


@dataclass
class ArmStats:
    """Latency/goodput accumulator (per-replica uniform completion ramp)."""

    slo_s: float
    drift_window: Tuple[int, int]
    lat_chunks: List[np.ndarray] = field(default_factory=list)
    good = 0
    total = 0
    seg_good = 0
    seg_total = 0
    epoch_walls: List[float] = field(default_factory=list)

    def record(self, epoch: int, counts: np.ndarray, busy: np.ndarray) -> None:
        in_seg = self.drift_window[0] <= epoch < self.drift_window[1]
        for c, b in zip(counts.astype(int), busy):
            if c <= 0:
                continue
            lat = b * np.arange(1, c + 1, dtype=np.float64) / c
            self.lat_chunks.append(lat)
            g = int((lat <= self.slo_s).sum())
            self.good += g
            self.total += c
            if in_seg:
                self.seg_good += g
                self.seg_total += c
        self.epoch_walls.append(float(busy.max()) if len(busy) else 0.0)

    def summary(self) -> Dict[str, float]:
        lat = np.concatenate(self.lat_chunks) if self.lat_chunks else np.zeros(1)
        return {
            "latency_p50_s": float(np.percentile(lat, 50)),
            "latency_p99_s": float(np.percentile(lat, 99)),
            "goodput": self.good / max(self.total, 1),
            "goodput_drift_segment": self.seg_good / max(self.seg_total, 1),
            "mean_epoch_wall_s": float(np.mean(self.epoch_walls)),
            "serving_sim_s": float(np.sum(self.epoch_walls)),
            "chunks_served": int(self.total),
        }


def active_rids(cfg: TraceConfig, epoch: int, quarantined: set) -> List[int]:
    """Scripted membership (join/leave) minus adaptive quarantines."""
    rids = [i for i in range(len(cfg.replicas))]
    if cfg.join is not None and epoch >= cfg.join[2]:
        rids.append(len(cfg.replicas))  # the joiner gets the next id
    if cfg.leave is not None and epoch >= cfg.leave[1]:
        rids = [r for r in rids if r != cfg.leave[0]]
    return [r for r in rids if r not in quarantined]


def world_with_joiner(cfg: TraceConfig, world: World) -> World:
    if cfg.join is not None:
        cls, speed, _at = cfg.join
        world.replicas.append(
            Replica(
                rid=len(cfg.replicas), cls=cls, base=speed,
                phase=0.61803 * (len(cfg.replicas) + 1) * 2.0 * math.pi,
            )
        )
    return world


def slo_seconds(cfg: TraceConfig) -> float:
    cap0 = sum(s for _, s in cfg.replicas)
    mean_arrivals = sum(cfg.tenants.values())
    return cfg.slo_factor * mean_arrivals / cap0


def prewarm_fleet_shapes(cfg: TraceConfig) -> None:
    """Precompile the stacked programs for every (q, p) the scripted trace
    can produce.  With ``reserve_knots`` the carry's shapes are fully
    predictable ([q, p, RESERVE_KNOTS]), so a serving deployment compiles
    them once at startup — the standard serving warm-up — and membership
    changes mid-trace never pay a jit trace."""
    from repro.core.fpm import PiecewiseLinearFPM
    from repro.core.modelbank_jax import JaxModelBank

    p0 = len(cfg.replicas)
    qs = {len(cfg.tenants)}
    if cfg.admit is not None:
        qs.add(len(cfg.tenants) + 1)
    ps = {p0, p0 - 1}
    if cfg.join is not None:
        ps.add(p0 + 1)
    for q in sorted(qs):
        for p in sorted(ps):
            banks = [
                JaxModelBank.from_models(
                    [PiecewiseLinearFPM.from_points([(8.0, 1.0), (16.0, 1.0)])
                     for _ in range(p)]
                )
                for _ in range(q)
            ]
            st = JaxModelBank.stack(banks, min_k=RESERVE_KNOTS)
            n = np.full(q, 4 * p, dtype=np.int64)
            caps = np.full((q, p), 4 * p, dtype=np.int64)
            mu = np.ones(q, dtype=np.int64)
            st.monotone_lanes()
            for lanes in (np.ones(q, dtype=bool), np.zeros(q, dtype=bool)):
                st.partition_units(n, caps, min_units=mu, completion_lanes=lanes)
            st.fold_in(
                np.full((q, p), 8.0), np.ones((q, p)), np.ones((q, p), dtype=bool)
            )


# ---------------------------------------------------------------------------
# the three arms
# ---------------------------------------------------------------------------


def run_reference_arm(cfg: TraceConfig, world: World, trace, *, oracle: bool):
    """static (deploy-time speeds, frozen) or oracle (true drifted speeds)."""
    stats = ArmStats(slo_s=slo_seconds(cfg), drift_window=cfg.drift_step[1:3])
    deploy_speed: Dict[int, float] = {}
    for e in range(cfg.epochs):
        rids = active_rids(cfg, e, quarantined=set())
        for r in rids:
            deploy_speed.setdefault(r, world.speed(r, e))  # measured on join
        true = world.speeds(rids, e)
        w = true if oracle else np.asarray([deploy_speed[r] for r in rids])
        counts = np.zeros(len(rids), dtype=np.int64)
        busy = np.zeros(len(rids), dtype=np.float64)
        for name, n in trace[e].items():
            d = prop_split(n, w)
            counts += d
            busy += np.where(d > 0, d / true, 0.0)
        stats.record(e, counts, busy)
    return stats.summary()


def run_adaptive_arm(cfg: TraceConfig, world: World, trace,
                     flight_path: Optional[str] = None):
    """The repo's serving loop, end to end (see module docstring).

    With ``flight_path`` set (and a ``FlightRecorder`` installed as the
    active telemetry sink), per-epoch estimate snapshots feed the recorder
    and a QUARANTINE verdict dumps the incident file immediately."""
    from repro import obs

    flight = obs.active() if flight_path is not None else None
    if not isinstance(flight, obs.FlightRecorder):
        flight = None
    stats = ArmStats(slo_s=slo_seconds(cfg), drift_window=cfg.drift_step[1:3])
    noise_rng = np.random.default_rng(cfg.seed + 1)
    registry = ProfileRegistry()
    quarantined: set = set()
    events: List[Dict[str, object]] = []
    sched_host = 0.0

    state = {"epoch": 0, "rids": active_rids(cfg, 0, quarantined)}

    def replica_run(i: int, x: int) -> float:
        rid = state["rids"][i]
        t = x / world.speed(rid, state["epoch"])
        return float(t * (1.0 + 0.02 * noise_rng.standard_normal()))

    disp = ReplicaDispatcher(
        replica_run=replica_run, num_replicas=len(state["rids"]), eps=0.08
    )

    def classes() -> List[str]:
        by_id = {r.rid: r.cls for r in world.replicas}
        return [by_id[r] for r in state["rids"]]

    def call_balance(tenants: Dict[str, int], max_iter: int) -> float:
        """One balance_fleet call; returns scheduler host seconds (the call
        wall minus the time spent inside replica_run — i.e. serving)."""
        t0 = time.perf_counter()
        e0 = disp.exec_host_s
        disp.balance_fleet(
            tenants,
            registry=registry,
            device_classes=classes(),
            workloads={name: "serve" for name in tenants},
            reserve_knots=RESERVE_KNOTS,
            quantize=QUANTIZE,
            staleness_tol=STALENESS_TOL,
            min_units=1,
            max_iter=max_iter,
        )
        return (time.perf_counter() - t0) - (disp.exec_host_s - e0)

    # -- setup (reported, excluded from the per-epoch overhead metric):
    #    precompile the predictable fleet shapes, then converge the tenants
    t_setup = time.perf_counter()
    prewarm_fleet_shapes(cfg)
    sched_setup = call_balance(trace[0], max_iter=12)
    setup_wall = time.perf_counter() - t_setup

    # -- gate (c): repeated warm call — identity, no restack, no compile ----
    import repro.core.modelbank_jax as mbj

    fleet0 = disp.fleet
    caches0 = (mbj._partition_units_jit._cache_size(), mbj._fold_in_jit._cache_size())
    restacks0 = fleet0.restacks
    call_balance(trace[0], max_iter=12)
    warm_gate = {
        "session_reused": disp.fleet is fleet0,
        "new_restacks": disp.fleet.restacks - restacks0,
        "new_partition_compiles": mbj._partition_units_jit._cache_size() - caches0[0],
        "new_fold_compiles": mbj._fold_in_jit._cache_size() - caches0[1],
    }
    warm_gate["ok"] = bool(
        warm_gate["session_reused"]
        and warm_gate["new_restacks"] == 0
        and warm_gate["new_partition_compiles"] == 0
        and warm_gate["new_fold_compiles"] == 0
    )

    straggler_rid = cfg.straggler[0]
    drift_rid = cfg.drift_step[0]
    share_pre_drift = None
    reaction: Dict[str, Optional[float]] = {
        "reprofile_epoch": None, "quarantine_epoch": None, "drift_epoch": None,
    }
    wrong_replica_events = 0
    # one self-healing REPROFILE shortly after a fresh-from-registry session
    # is the detector doing its job (clearing a stale merged class profile);
    # the same action in steady state is a misfire and counts as wrong
    grace_reprofiles = 0
    last_fresh_epoch = -10**9
    prev_tenants = set(trace[0])

    for e in range(cfg.epochs):
        state["epoch"] = e
        rids = active_rids(cfg, e, quarantined)
        tenants = dict(trace[e])
        membership = rids != state["rids"] or set(tenants) != prev_tenants
        prev_tenants = set(tenants)

        if membership:
            p_changed = len(rids) != len(state["rids"])
            old_fleet, old_rids = disp.fleet, state["rids"]
            state["rids"] = rids
            disp.num_replicas = len(rids)
            # one measured round IS this epoch's serving (no separate
            # rebalance/observe; the straggler scan pauses for the epoch)
            sched_host += call_balance(tenants, max_iter=1)
            if p_changed:
                last_fresh_epoch = e
            if p_changed and old_fleet is not None:
                # fresh session: strikes follow the survivors (remap — the
                # resize bugfix exercised at fleet scope)
                det = getattr(old_fleet, "detector", None)
                if det is not None:
                    surviving = [
                        j for j, r in enumerate(old_rids) if r in rids
                    ]
                    joined = len(rids) - len(surviving)
                    disp.fleet.detector = det.remap(surviving, joined)
            log = disp.logs[-1]
            assert isinstance(log, FleetRoundLog)
            counts = np.asarray(log.D, dtype=np.int64).sum(axis=0)
            busy = np.asarray(log.proc_busy, dtype=np.float64)
            stats.record(e, counts, busy)
            events.append({"epoch": e, "event": "membership",
                           "replicas": list(rids), "tenants": sorted(tenants)})
            continue

        fleet = disp.fleet
        t0 = time.perf_counter()
        ds = fleet.rebalance({name: int(n) for name, n in tenants.items()})
        sched_host += time.perf_counter() - t0

        true = world.speeds(rids, e)
        times: Dict[str, List[float]] = {}
        counts = np.zeros(len(rids), dtype=np.int64)
        busy = np.zeros(len(rids), dtype=np.float64)
        for name, d in ds.items():
            d = np.asarray(d, dtype=np.int64)
            t = np.where(d > 0, d / true, 0.0)
            t *= 1.0 + 0.02 * noise_rng.standard_normal(len(rids))
            t = np.where(d > 0, np.maximum(t, 1e-12), 0.0)
            times[name] = [float(v) for v in t]
            counts += d
            busy += t
        stats.record(e, counts, busy)
        if flight is not None:
            flight.snapshot(f"epoch:{e}", {
                "replicas": [int(r) for r in rids],
                "busy_s": [float(b) for b in busy],
                "allocations": {nm: [int(v) for v in d]
                                for nm, d in ds.items()},
            })

        t0 = time.perf_counter()
        acts = fleet.straggler_actions(times)  # pre-fold predictions
        fleet.observe(times)  # folds on the fleet's construction-time grid
        sched_host += time.perf_counter() - t0

        for i, act in enumerate(acts):
            if act is StragglerAction.NONE:
                continue
            rid = rids[i]
            events.append({"epoch": e, "event": act.value, "replica": rid})
            if act is StragglerAction.REPROFILE:
                if rid == straggler_rid:
                    if reaction["reprofile_epoch"] is None and e >= cfg.straggler[1]:
                        reaction["reprofile_epoch"] = e
                elif rid == drift_rid:
                    pass  # drift step legitimately reprofiles, never quarantines
                elif e - last_fresh_epoch <= REACTION_BOUND_EPOCHS:
                    grace_reprofiles += 1  # clearing a stale warm profile
                else:
                    wrong_replica_events += 1
            if act is StragglerAction.QUARANTINE:
                if flight is not None:
                    det = getattr(fleet, "detector", None)
                    rows = [r for r in (det.history if det else []) if r[0] == i]
                    flight.dump(
                        flight_path,
                        reason="quarantine",
                        context={
                            "replica": int(rid),
                            "epoch": int(e),
                            "strike_evidence": [
                                {"d_units": int(du), "predicted": float(pr),
                                 "observed": float(ob), "ratio": float(ra)}
                                for _, du, pr, ob, ra in rows[-5:]
                            ],
                        },
                    )
                if rid == straggler_rid:
                    if reaction["quarantine_epoch"] is None:
                        reaction["quarantine_epoch"] = e
                    quarantined.add(rid)
                else:
                    wrong_replica_events += 1

        # drift reaction: share on the stepped replica visibly drops
        if drift_rid in rids:
            i = rids.index(drift_rid)
            share = sum(d[i] for d in ds.values()) / max(sum(tenants.values()), 1)
            if e == cfg.drift_step[1] - 1:
                share_pre_drift = share
            if (
                reaction["drift_epoch"] is None
                and share_pre_drift is not None
                and e >= cfg.drift_step[1]
                and share < 0.8 * share_pre_drift
            ):
                reaction["drift_epoch"] = e

    out = stats.summary()
    dt = cfg.dt
    out.update({
        "setup_wall_s": setup_wall,
        "setup_sched_host_s": sched_setup,
        "sched_host_s": sched_host,
        "rebalance_overhead_frac": sched_host / max(out["serving_sim_s"], 1e-12),
        "straggler_replica": straggler_rid,
        "straggler_onset_epoch": cfg.straggler[1],
        "reprofile_reaction_s": (
            (reaction["reprofile_epoch"] - cfg.straggler[1] + 1) * dt
            if reaction["reprofile_epoch"] is not None else None
        ),
        "quarantine_reaction_s": (
            (reaction["quarantine_epoch"] - cfg.straggler[1] + 1) * dt
            if reaction["quarantine_epoch"] is not None else None
        ),
        "drift_reaction_s": (
            (reaction["drift_epoch"] - cfg.drift_step[1] + 1) * dt
            if reaction["drift_epoch"] is not None else None
        ),
        "quarantined_replica": (
            next(iter(quarantined)) if quarantined else None
        ),
        "wrong_replica_events": wrong_replica_events,
        "grace_reprofiles": grace_reprofiles,
        "warm_no_recompile": warm_gate,
        "events": events,
    })
    return out


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: short trace, gates only")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--trace", default=None, metavar="TRACE",
                    help="write a Chrome-trace JSON of the adaptive arm "
                         "(+ TRACE.flightrec.json on incidents)")
    args = ap.parse_args(argv)
    use_compile_cache()

    # benchmark-process only (NOT at import: the test suite imports this
    # module, and flipping x64 mid-process would change every other test)
    jax.config.update("jax_enable_x64", True)

    cfg = QUICK if args.quick else FULL
    world = world_with_joiner(cfg, build_world(cfg))
    trace = build_trace(cfg)

    print(f"trace: {cfg.epochs} epochs x {cfg.dt}s, "
          f"{len(cfg.replicas)} replicas, seed={cfg.seed}", flush=True)
    static = run_reference_arm(cfg, world, trace, oracle=False)
    oracle = run_reference_arm(cfg, world, trace, oracle=True)
    tel = None
    flight_path = None
    if args.trace:
        from repro import obs

        flight_path = args.trace + ".flightrec.json"
        # ring big enough to hold the whole session (the recorder bound
        # matters for hours-long deployments, not a bounded benchmark)
        tel = obs.FlightRecorder(capacity=200_000, snapshot_capacity=64)
        obs.install(tel)
    try:
        adaptive = run_adaptive_arm(cfg, world, trace, flight_path=flight_path)
    finally:
        if tel is not None:
            from repro import obs

            obs.uninstall()

    for name, row in (("static", static), ("oracle", oracle),
                      ("adaptive", adaptive)):
        print(f"{name:9s} p50 {row['latency_p50_s']:.3f}s "
              f"p99 {row['latency_p99_s']:.3f}s goodput {row['goodput']:.3f} "
              f"(drift seg {row['goodput_drift_segment']:.3f})", flush=True)
    print(f"adaptive  reaction: reprofile {adaptive['reprofile_reaction_s']}s, "
          f"quarantine {adaptive['quarantine_reaction_s']}s "
          f"(replica {adaptive['quarantined_replica']}, "
          f"wrong-replica events {adaptive['wrong_replica_events']}, "
          f"grace reprofiles {adaptive['grace_reprofiles']}), "
          f"drift {adaptive['drift_reaction_s']}s", flush=True)
    print(f"adaptive  overhead: {adaptive['sched_host_s']:.3f}s host / "
          f"{adaptive['serving_sim_s']:.1f}s served "
          f"= {adaptive['rebalance_overhead_frac']:.4%} "
          f"(setup {adaptive['setup_sched_host_s']:.3f}s excluded)", flush=True)

    rc = 0
    g = adaptive
    if g["goodput_drift_segment"] < static["goodput_drift_segment"]:
        print("FAIL(a): adaptive drift-segment goodput "
              f"{g['goodput_drift_segment']:.3f} < static "
              f"{static['goodput_drift_segment']:.3f}")
        rc = 1
    if g["latency_p99_s"] >= static["latency_p99_s"]:
        print(f"FAIL(a): adaptive p99 {g['latency_p99_s']:.3f}s >= "
              f"static {static['latency_p99_s']:.3f}s")
        rc = 1
    bound_s = REACTION_BOUND_EPOCHS * cfg.dt
    if g["reprofile_reaction_s"] is None or g["reprofile_reaction_s"] > bound_s:
        print(f"FAIL(b): straggler REPROFILE reaction "
              f"{g['reprofile_reaction_s']} not within {bound_s}s")
        rc = 1
    if g["quarantined_replica"] != g["straggler_replica"]:
        print(f"FAIL(b): quarantined replica {g['quarantined_replica']} != "
              f"throttled replica {g['straggler_replica']}")
        rc = 1
    if g["wrong_replica_events"]:
        print(f"FAIL(b): {g['wrong_replica_events']} straggler actions fired "
              "on healthy replicas")
        rc = 1
    if not g["warm_no_recompile"]["ok"]:
        print(f"FAIL(c): warm balance_fleet recompiled: "
              f"{g['warm_no_recompile']}")
        rc = 1
    if g["rebalance_overhead_frac"] > OVERHEAD_BOUND:
        print(f"FAIL(d): rebalance overhead "
              f"{g['rebalance_overhead_frac']:.4%} > {OVERHEAD_BOUND:.0%}")
        rc = 1

    if tel is not None:
        from repro.obs.chrometrace import export_chrome_trace

        # The canonical overhead gauge is the harness's own full-session
        # fraction (the paper's headline figure); the dispatcher's live
        # "serve.split.*" gauges are the per-balance view of the same split.
        tel.gauge("serve.rebalance_overhead_frac",
                  float(adaptive["rebalance_overhead_frac"]))
        if adaptive["reprofile_reaction_s"] is not None:
            tel.gauge("serve.reaction_epochs",
                      float(adaptive["reprofile_reaction_s"]) / cfg.dt)
        export_chrome_trace(tel, args.trace)
        with open(args.trace) as f:
            parsed = json.load(f)  # must round-trip as valid JSON
        fleet_spans = sum(
            1 for ev in parsed.get("traceEvents", [])
            if ev.get("ph") == "X" and ev.get("cat") == "fleet"
        )
        gauge = parsed.get("repro", {}).get("gauges", {}).get(
            "serve.rebalance_overhead_frac"
        )
        print(f"trace: {len(parsed.get('traceEvents', []))} events, "
              f"{fleet_spans} fleet spans over {cfg.epochs} epochs "
              f"-> {args.trace}", flush=True)
        if fleet_spans < cfg.epochs:
            print(f"FAIL(trace): {fleet_spans} fleet spans < "
                  f"{cfg.epochs} epochs (expected >= 1 per round)")
            rc = 1
        if gauge is None or abs(
            gauge - adaptive["rebalance_overhead_frac"]
        ) > 1e-12:
            print(f"FAIL(trace): trace overhead gauge {gauge!r} != harness "
                  f"fraction {adaptive['rebalance_overhead_frac']!r}")
            rc = 1
        if rc != 0:
            tel.dump(flight_path, reason="gate-failure",
                     context={"gates_ok": False})
            print(f"-> {flight_path} (gate failure)")

    if rc == 0:
        print("all gates OK")

    payload = {
        "benchmark": "serve_trace",
        "description": (
            "traffic-trace serving harness: seeded Poisson+diurnal+flash "
            "arrivals drive ReplicaDispatcher.balance_fleet warm sessions "
            "over a drifting heterogeneous replica fleet with a runaway "
            "straggler (REPROFILE->QUARANTINE on the right replica), tenant "
            "admit/retire and replica join/leave; adaptive vs static "
            "(deploy-time speeds) vs oracle (true drifted speeds); latency "
            "model = time-sliced per-replica busy sums (FleetRoundLog), "
            "chunk j of c on a replica completes at busy*j/c; overhead = "
            "scheduler host seconds / simulated serving seconds"
        ),
        "mode": "quick" if args.quick else "full",
        "config": {
            "epochs": cfg.epochs, "dt_s": cfg.dt, "seed": cfg.seed,
            "replicas": [{"rid": i, "class": c, "base_speed": s}
                         for i, (c, s) in enumerate(cfg.replicas)],
            "tenants": cfg.tenants, "slo_s": slo_seconds(cfg),
            "drift_step": cfg.drift_step, "straggler": cfg.straggler,
            "flash": cfg.flash, "admit": cfg.admit,
            "join": cfg.join, "leave": cfg.leave,
            "reaction_bound_s": bound_s,
        },
        "arms": {"static": static, "oracle": oracle, "adaptive": adaptive},
        "gates_ok": rc == 0,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"-> {args.out}")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
