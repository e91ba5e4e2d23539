"""The telemetry subsystem (PR 10): sink API, Chrome-trace export, flight
recorder, and — most importantly — the two invariants the instrumentation
must never break:

* DISABLED is free: with no sink installed (the default), every
  instrumented layer makes ZERO obs-layer calls (proven with a counting
  stub) and produces bit-identical results to a build where the obs
  package is absent (proven by monkeypatching every module's guarded
  ``_obs_active`` hook to ``None``).
* Telemetry is read-only: running the SAME work with and without a sink
  yields identical allocations/histories — recording never perturbs the
  schedule.

Plus the satellite regressions: the public ``FleetScheduler.stats()``
counter snapshot (deterministic serving replay must report
``speculative_misses == 0`` — every depth-1 speculative read is consumed
when serving tenants never populate seen sets), registry warnings mirrored
as structured events without changing warning behaviour, the
injectable-clock ``t_wall`` stamps on the typed serving log, and the
flight-recorder dump naming a quarantined replica with strike evidence.
"""

import json
import warnings

import numpy as np
import pytest

import jax

from repro import obs
from repro.core import PiecewiseLinearFPM
from repro.fleet import FleetScheduler, JobSpec, ProfileRegistry
from repro.obs.chrometrace import to_chrome_trace
from repro.obs.report import MetricsSnapshot
from repro.runtime.serve_loop import ReplicaDispatcher
from repro.runtime.straggler import StragglerAction, StragglerDetector


# ---------------------------------------------------------------------------
# helpers: a small serving fleet under warm models
# ---------------------------------------------------------------------------

P, Q = 6, 3


def _warm_models(base_row):
    return [
        PiecewiseLinearFPM.from_points([(1.0, 1.0 / b), (1e6, 1.0 / b)])
        for b in base_row
    ]


def _mk_serving_fleet(backend="numpy", **kw):
    rng = np.random.default_rng(7)
    base = rng.uniform(1e-4, 5e-4, (Q, P))
    fleet = FleetScheduler(P, backend=backend, **kw)
    for j in range(Q):
        fleet.admit(
            JobSpec(name=f"t{j}", n=400 + 3 * j, eps=0.05, min_units=1),
            models=_warm_models(base[j]),
        )
    return fleet, base


def _serve_epochs(fleet, base, epochs=5):
    """Deterministic serving replay: rebalance + observe, no noise."""
    for _ in range(epochs):
        ds = fleet.rebalance()
        times = {
            f"t{j}": [x * base[j, i] if x > 0 else 0.0
                      for i, x in enumerate(ds[f"t{j}"])]
            for j in range(Q)
        }
        fleet.observe(times)
    return fleet


# ---------------------------------------------------------------------------
# Telemetry sink API
# ---------------------------------------------------------------------------


def test_telemetry_records_all_kinds():
    tel = obs.Telemetry()
    tel.span_at("work", 1.0, 1.5, n=3)
    tel.counter("hits")
    tel.counter("hits", 2)
    tel.gauge("theta", 0.25)
    tel.gauge("theta", 0.75)  # last value wins
    tel.event("boom", who="r2")
    assert tel.enabled
    assert tel.counters["hits"] == 3
    assert tel.gauges["theta"] == 0.75
    spans = tel.spans()
    assert [s.name for s in spans] == ["work"]
    assert spans[0].t1 - spans[0].t0 == pytest.approx(0.5)
    assert spans[0].attrs == {"n": 3}
    kinds = sorted(e.kind for e in tel.events)
    assert kinds == ["counter", "counter", "event", "gauge", "gauge", "span"]
    payload = tel.to_payload()
    assert payload["counters"]["hits"] == 3
    tel.clear()
    assert not tel.events and not tel.counters and not tel.gauges


def test_telemetry_ring_bound():
    tel = obs.Telemetry(capacity=4)
    for i in range(10):
        tel.event("e", i=i)
    assert len(tel.events) == 4
    assert [e.attrs["i"] for e in tel.events] == [6, 7, 8, 9]
    # counters/gauges aggregate regardless of the ring
    for i in range(10):
        tel.counter("c")
    assert tel.counters["c"] == 10


def test_install_active_use():
    assert obs.active() is obs.NOOP
    assert not obs.NOOP.enabled
    tel = obs.Telemetry()
    obs.install(tel)
    try:
        assert obs.active() is tel
    finally:
        obs.uninstall()
    assert obs.active() is obs.NOOP
    with obs.use(tel) as got:
        assert got is tel and obs.active() is tel
    assert obs.active() is obs.NOOP
    # NOOP swallows every call without recording
    obs.NOOP.span_at("x", 0.0, 1.0)
    obs.NOOP.end(obs.NOOP.begin("x"))
    obs.NOOP.counter("x")
    obs.NOOP.gauge("x", 1.0)
    obs.NOOP.event("x")


# ---------------------------------------------------------------------------
# instrumented layers record; recording never perturbs results
# ---------------------------------------------------------------------------


def test_fleet_serving_records_spans_and_gauges():
    tel = obs.Telemetry()
    fleet, base = _mk_serving_fleet()
    with obs.use(tel):
        _serve_epochs(fleet, base, epochs=3)
    names = {e.name for e in tel.spans()}
    assert {"fleet.rebalance", "fleet.observe"} <= names
    # every stats() field is exported as a fleet.* gauge each round
    for key, val in fleet.stats().items():
        assert tel.gauges[f"fleet.{key}"] == val


def test_telemetry_is_read_only():
    fa, base = _mk_serving_fleet()
    fb, _ = _mk_serving_fleet()
    with obs.use(obs.Telemetry()):
        _serve_epochs(fa, base, epochs=4)
    _serve_epochs(fb, base, epochs=4)
    for j in range(Q):
        assert fa.snapshot(f"t{j}").allocations == fb.snapshot(f"t{j}").allocations
    assert fa.stats() == fb.stats()


class _CountingDisabledSink:
    """enabled=False stub: any recording call is an instrumentation bug."""

    enabled = False

    def __init__(self):
        self.calls = 0

    def _bump(self, *a, **k):
        self.calls += 1

    span = span_at = counter = gauge = event = begin = end = _bump
    clock = staticmethod(lambda: 0.0)


def test_disabled_sink_means_zero_obs_calls():
    """Every site must check ``enabled`` BEFORE calling any recording
    method — the disabled path does zero obs-layer work."""
    stub = _CountingDisabledSink()
    obs.install(stub)
    try:
        fleet, base = _mk_serving_fleet()
        _serve_epochs(fleet, base, epochs=3)
    finally:
        obs.uninstall()
    assert stub.calls == 0


def test_absent_obs_package_bit_identical(monkeypatch):
    """Simulate the obs package being absent (every guarded ``_obs_active``
    hook returns None, as the ImportError fallback does) and require
    bit-identical serving results."""
    import repro.core.hierarchy as hierarchy
    import repro.core.scheduler as core_scheduler
    import repro.core.speedstore as speedstore
    import repro.fleet.registry as registry
    import repro.fleet.scheduler as fleet_scheduler
    import repro.runtime.serve_loop as serve_loop
    import repro.runtime.straggler as straggler

    fa, base = _mk_serving_fleet()
    _serve_epochs(fa, base, epochs=4)

    for mod in (fleet_scheduler, core_scheduler, speedstore, hierarchy,
                registry, serve_loop, straggler):
        monkeypatch.setattr(mod, "_obs_active", lambda: None)
    fb, _ = _mk_serving_fleet()
    _serve_epochs(fb, base, epochs=4)

    for j in range(Q):
        assert fa.snapshot(f"t{j}").allocations == fb.snapshot(f"t{j}").allocations
    assert fa.stats() == fb.stats()


# ---------------------------------------------------------------------------
# public stats(): the satellite regression
# ---------------------------------------------------------------------------


def test_stats_shape_and_types():
    fleet, base = _mk_serving_fleet()
    _serve_epochs(fleet, base, epochs=2)
    st = fleet.stats()
    assert set(st) == {
        "rounds", "restacks", "device_dispatches", "predispatches",
        "stale_reads", "speculation_hits", "speculative_misses",
    }
    assert all(isinstance(v, int) for v in st.values())
    assert st["rounds"] == fleet.rounds
    assert st["speculation_hits"] == st["stale_reads"]


def test_deterministic_serving_replay_has_zero_speculative_misses():
    """Depth-1 pipelined serving: the pre-dispatched partition reads the
    previous carry speculatively, but serving tenants (admitted with
    learned models, never measuring) keep empty seen sets — every
    speculative read must be CONSUMED, none discarded."""
    with jax.enable_x64():
        fleet, base = _mk_serving_fleet(backend="jax", pipeline=True,
                                        pipeline_depth=1)
        _serve_epochs(fleet, base, epochs=6)
    st = fleet.stats()
    assert st["speculative_misses"] == 0
    assert st["stale_reads"] > 0  # the pipeline really speculated
    assert st["predispatches"] > 0


# ---------------------------------------------------------------------------
# Chrome trace + report
# ---------------------------------------------------------------------------


def test_chrome_trace_is_valid(tmp_path):
    tel = obs.Telemetry()
    fleet, base = _mk_serving_fleet()
    with obs.use(tel):
        _serve_epochs(fleet, base, epochs=3)
        tel.gauge("demo.gauge", 0.5)
    path = tmp_path / "trace.json"
    obs.export_chrome_trace(tel, str(path))
    trace = json.loads(path.read_text())
    evs = trace["traceEvents"]
    assert evs, "empty trace"
    for e in evs:
        assert e["ph"] in ("X", "C", "i", "M")
        assert "name" in e and "pid" in e
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and all(e["dur"] >= 0 and "ts" in e for e in xs)
    assert {e["name"] for e in xs} >= {"fleet.rebalance", "fleet.observe"}
    # the sidecar block carries the aggregates for repro.obs.report
    assert trace["repro"]["gauges"]["demo.gauge"] == 0.5
    assert trace["repro"]["gauges"]["fleet.rounds"] == fleet.rounds


def test_report_snapshot_roundtrip(tmp_path):
    tel = obs.Telemetry()
    fleet, base = _mk_serving_fleet()
    with obs.use(tel):
        _serve_epochs(fleet, base, epochs=3)
    path = tmp_path / "trace.json"
    obs.export_chrome_trace(tel, str(path))
    snap = MetricsSnapshot.from_file(str(path))
    assert snap.rounds == fleet.rounds
    assert snap.speculative_misses == fleet.speculative_misses
    table = snap.table()
    assert "rounds" in table and "span wall totals" in table
    # the module CLI parses the same file (smoke the __main__ path)
    from repro.obs import report
    assert report.main([str(path)]) == 0


def test_lazy_metrics_snapshot_attribute():
    import repro.obs as pkg
    assert pkg.MetricsSnapshot is MetricsSnapshot
    with pytest.raises(AttributeError):
        pkg.no_such_symbol


# ---------------------------------------------------------------------------
# registry warnings -> structured events (behaviour unchanged)
# ---------------------------------------------------------------------------


def test_registry_warning_mirrored_as_event(tmp_path):
    tel = obs.Telemetry()
    missing = str(tmp_path / "nope.json")
    with obs.use(tel):
        with pytest.warns(UserWarning, match="not found"):
            reg = ProfileRegistry.load(missing)
    assert isinstance(reg, ProfileRegistry)
    evs = [e for e in tel.events if e.name == "registry.warning"]
    assert len(evs) == 1
    assert evs[0].attrs["kind"] == "not_found"
    assert evs[0].attrs["path"] == missing
    assert "not found" in evs[0].attrs["message"]


def test_registry_warning_fires_without_telemetry(tmp_path):
    # no sink installed: the warning still fires, nothing else happens
    with pytest.warns(UserWarning, match="unreadable|Expecting|malformed"):
        bad = tmp_path / "bad.json"
        bad.write_text("{nope")
        ProfileRegistry.load(str(bad))


def test_registry_malformed_entry_event():
    tel = obs.Telemetry()
    reg = ProfileRegistry()
    reg._entries[("cpu", "matmul")] = "garbage"  # corrupt one entry in place
    with obs.use(tel):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            out = reg.get("cpu", "matmul")
    assert out is None
    assert any(issubclass(x.category, UserWarning) for x in w)
    evs = [e for e in tel.events if e.name == "registry.warning"]
    assert evs and evs[0].attrs["kind"] == "malformed_entry"
    assert evs[0].attrs["device_class"] == "cpu"


# ---------------------------------------------------------------------------
# straggler events + flight recorder
# ---------------------------------------------------------------------------


def _quarantine_under(sink):
    det = StragglerDetector(factor=1.5, patience=3, patience_hard=6)
    model = PiecewiseLinearFPM.from_points([(1.0, 1000.0), (100.0, 1000.0)])
    with obs.use(sink):
        for _ in range(8):
            act = det.update(2, model, d_units=10, observed_t=0.04)
            if act is StragglerAction.QUARANTINE:
                return act
    return act


def test_straggler_strike_events_carry_evidence():
    tel = obs.Telemetry()
    act = _quarantine_under(tel)
    assert act is StragglerAction.QUARANTINE
    strikes = [e for e in tel.events if e.name == "straggler.strike"]
    verdicts = [e for e in tel.events if e.name == "straggler.verdict"]
    assert strikes and verdicts
    ev = strikes[-1].attrs
    assert ev["group"] == 2
    assert ev["ratio"] == pytest.approx(4.0)
    assert ev["predicted"] == pytest.approx(0.01)
    assert ev["observed"] == pytest.approx(0.04)
    assert verdicts[-1].attrs["action"] == "quarantine"
    assert tel.counters["straggler.quarantine"] == 1


def test_flight_recorder_dump_names_offender(tmp_path):
    flight = obs.FlightRecorder(capacity=64, snapshot_capacity=4)
    flight.snapshot("pre", {"allocations": [10, 10, 10]})
    act = _quarantine_under(flight)
    assert act is StragglerAction.QUARANTINE
    path = tmp_path / "incident.flightrec.json"
    flight.dump(str(path), reason="quarantine",
                context={"replica": 2, "epoch": 5})
    dump = json.loads(path.read_text())
    assert dump["kind"] == "flight-recorder"
    assert dump["reason"] == "quarantine"
    assert dump["context"]["replica"] == 2
    assert dump["snapshots"][0]["label"] == "pre"
    strikes = [e for e in dump["events"] if e["name"] == "straggler.strike"]
    assert strikes and strikes[-1]["attrs"]["group"] == 2
    assert strikes[-1]["attrs"]["observed"] == pytest.approx(0.04)


def test_flight_recorder_ring_and_snapshot_bounds():
    flight = obs.FlightRecorder(capacity=8, snapshot_capacity=2)
    for i in range(20):
        flight.event("e", i=i)
        flight.snapshot(f"s{i}", {"i": i})
    assert len(flight.events) == 8
    assert len(flight.snapshots) == 2
    assert [s["label"] for s in flight.snapshots] == ["s18", "s19"]


# ---------------------------------------------------------------------------
# typed serving log: t_wall stamps from an injectable clock
# ---------------------------------------------------------------------------


def test_serving_log_t_wall_monotonic_from_injected_clock():
    base = [4e-4, 2e-4, 8e-4, 3e-4]

    def replica_run(i, x):
        return x * base[i] if x > 0 else 0.0

    ticks = iter(np.arange(100.0, 200.0, 0.5))
    disp = ReplicaDispatcher(replica_run, 4, eps=0.15,
                             clock=lambda: float(next(ticks)))
    disp.balance(96)
    assert disp.logs, "balance() appended no rounds"
    stamps = [log.t_wall for log in disp.logs]
    assert all(b > a for a, b in zip(stamps, stamps[1:]))
    assert stamps[0] >= 100.0  # came from the injected clock
    # t_wall is excluded from equality: replay comparisons ignore it
    a = disp.logs[0]
    b = type(a)(**{**a.__dict__, "t_wall": -1.0})
    assert a == b


# ---------------------------------------------------------------------------
# program spans of a flat round: parents, the profiler's host plane, compile
# spans, the partition program's loop trips
# ---------------------------------------------------------------------------


class _TrueTimes:
    """Executor whose times come from fixed linear speeds."""

    def __init__(self, speeds):
        self.speeds = list(speeds)
        self.num_procs = len(speeds)

    def run(self, d):
        return [x / s if x > 0 else 0.0 for x, s in zip(d, self.speeds)]


def _flat_session(p=24, n=6000, backend="jax"):
    """A flat DFPA session: ``autotune`` to eps, then one served round
    that repartitions.  Returns the scheduler."""
    from repro.core import Policy, Scheduler, SpeedStore

    speeds = np.random.default_rng(3).uniform(1.0, 5.0, p)
    sched = Scheduler(SpeedStore.empty(p, backend=backend), policy=Policy.DFPA,
                      eps=0.02, min_units=1)
    sched.autotune(_TrueTimes(speeds), n, 0.02, max_iter=30, min_units=1)
    shifted = speeds * np.where(np.arange(p) < p // 4, 0.5, 1.0)
    sched.observe(_TrueTimes(shifted).run(sched.d))
    return sched


def _tree(tel):
    spans = {e.id: e for e in tel.spans()}

    def root_of(e):
        while e.parent in spans:
            e = spans[e.parent]
        return e

    return spans, root_of


def _inside(child, parent):
    return parent.t0 <= child.t0 and child.t1 <= parent.t1


def test_flat_round_span_tree():
    tel = obs.Telemetry()
    with jax.enable_x64(), obs.use(tel):
        _flat_session()
    spans, root_of = _tree(tel)
    by_name = {}
    for e in spans.values():
        by_name.setdefault(e.name, []).append(e)
    for e in spans.values():  # every child lies inside its parent
        if e.parent:
            assert _inside(e, spans[e.parent]), (e.name, spans[e.parent].name)

    # the served round: one root carrying its step
    (obs_root,) = by_name["scheduler.observe"]
    assert obs_root.parent == 0 and obs_root.attrs["step"] == 1
    assert obs_root.attrs["changed"] is True
    kids = sorted((e.t0, e.name) for e in spans.values() if e.parent == obs_root.id
                  and e.name != obs.COMPILE_SPAN)
    assert [n for _, n in kids] == ["scheduler.ema", "speedstore.fold_in",
                                    "speedstore.partition"]
    fold = next(e for e in spans.values()
                if e.name == "speedstore.fold_in" and e.parent == obs_root.id)
    assert sorted(e.name for e in spans.values() if e.parent == fold.id
                  and e.name != obs.COMPILE_SPAN) == ["modelbank.fold_in",
                                                      "speedstore.fold_host"]
    part = next(e for e in spans.values()
                if e.name == "speedstore.partition" and e.parent == obs_root.id)
    assert [e.name for e in spans.values() if e.parent == part.id
            and e.name != obs.COMPILE_SPAN] == ["modelbank.partition.wait"]

    # the session: measured rounds, each a root identified by its iteration
    (tune,) = by_name["scheduler.autotune"]
    rounds = sorted(by_name["scheduler.tune_round"], key=lambda e: e.t0)
    assert [r.attrs["iteration"] for r in rounds] == list(range(1, len(rounds) + 1))
    assert tune.attrs["iterations"] == len(rounds) >= 2
    assert all(r.parent == tune.id for r in rounds)
    for r in rounds:
        names = [e.name for e in sorted(spans.values(), key=lambda e: e.t0)
                 if e.parent == r.id and e.name != obs.COMPILE_SPAN]
        assert names[:2] == ["scheduler.measure", "speedstore.fold_in"]
        assert set(names) <= {"scheduler.measure", "speedstore.fold_in",
                              "speedstore.partition"}
    # every span of a round reaches that round's root
    for e in spans.values():
        if e.name in ("speedstore.fold_host", "modelbank.fold_in",
                      "modelbank.partition.wait", "scheduler.measure"):
            assert root_of(e).name in ("scheduler.observe", "scheduler.autotune")
            assert spans[e.parent].name != "scheduler.autotune"


def test_flat_jax_path_disabled_sink_makes_zero_obs_calls():
    stub = _CountingDisabledSink()
    obs.install(stub)
    try:
        with jax.enable_x64():
            _flat_session()
    finally:
        obs.uninstall()
    assert stub.calls == 0


def test_flat_path_bit_identical_with_telemetry():
    with jax.enable_x64():
        with obs.use(obs.Telemetry()):
            on = _flat_session()
        off = _flat_session()
        bank_on = on.store.device_bank(snapshot=False)
        bank_off = off.store.device_bank(snapshot=False)
        assert on.d == off.d
        for a, b in ((bank_on.xs, bank_off.xs), (bank_on.ss, bank_off.ss),
                     (bank_on.counts, bank_off.counts)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_program_spans_on_the_profilers_host_plane(tmp_path):
    with jax.enable_x64():
        sched = _flat_session()
        times = _TrueTimes([1.0] * sched.num_groups).run(sched.d)
        with obs.use(obs.Telemetry()):
            jax.profiler.start_trace(str(tmp_path))
            try:
                sched.observe(times)
            finally:
                jax.profiler.stop_trace()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    host = {e.name for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events}
    assert {"scheduler.observe", "speedstore.fold_in", "modelbank.fold_in"} <= host


def test_compile_span_names_function_and_enclosing_span(tmp_path):
    from repro.core import SpeedStore

    tel = obs.Telemetry()
    with jax.enable_x64(), obs.use(tel):
        store = SpeedStore.empty(37, backend="jax")  # a shape no other test folds
        with tel.span("test.enclosing"):
            store.fold_in(np.arange(1.0, 38.0), np.full(37, 2.0))
    spans, _ = _tree(tel)
    enclosing = next(e for e in spans.values() if e.name == "test.enclosing")
    compiles = [e for e in spans.values() if e.name == obs.COMPILE_SPAN
                and "_fold_in_impl" in e.attrs["fun_name"]]
    assert compiles, sorted({e.attrs.get("fun_name") for e in spans.values()})
    (fold,) = [e for e in spans.values() if e.name == "modelbank.fold_in"]
    assert all(e.parent == fold.id for e in compiles)
    assert spans[fold.parent].parent == enclosing.id  # speedstore.fold_in
    # the report counts them by the program's name
    path = tmp_path / "trace.json"
    obs.export_chrome_trace(tel, str(path))
    snap = MetricsSnapshot.from_file(str(path))
    assert snap.recompiles_fold == len(compiles)
    assert snap.fold_ins == 1


def _monotone_bank(p=16, seed=5):
    """A store of ``p`` increasing-speed piecewise models (monotone times)."""
    rng = np.random.default_rng(seed)
    return [
        PiecewiseLinearFPM.from_points(
            [(x, s * (1.0 + 0.1 * k)) for k, x in enumerate((1.0, 50.0, 400.0, 3000.0))]
        )
        for s in rng.uniform(1.0, 4.0, p)
    ]


def _trips(backend, completion, n=5000, min_units=1, models=None):
    from repro.core import SpeedStore

    store = SpeedStore.from_models(models or _monotone_bank(), backend=backend)
    tel = obs.Telemetry()
    with obs.use(tel):
        store.partition(n, min_units=min_units, completion=completion)
    return {k.split(".")[-1]: v for k, v in tel.counters.items()
            if k.startswith("partition.trips.")}


def test_partition_trip_counters_per_loop():
    from repro.core.modelbank_jax import TRIP_LOOPS

    with jax.enable_x64():
        greedy = _trips("jax", "greedy")
        fast = _trips("jax", "threshold")
        # min_units far above the continuous share of the slow rows: the
        # floor overshoots n and the take-back loop runs
        slow = _monotone_bank() + [PiecewiseLinearFPM.from_points([(1.0, 1e-3), (1e4, 1e-3)])]
        takeback = _trips("jax", "greedy", n=17 * 40, min_units=40, models=slow)
    assert set(greedy) == set(fast) == set(TRIP_LOOPS)
    assert greedy["bisection"] > 0 and greedy["greedy"] > 0
    # the static branch: no threshold completion compiled in greedy mode
    assert greedy["threshold_doubling"] == greedy["threshold_bisection"] == 0
    assert fast["threshold_bisection"] > 0
    assert fast["greedy"] < greedy["greedy"]  # the bulk grant leaves a few units
    assert greedy["takeback"] == 0 and takeback["takeback"] > 0


def test_numpy_bisection_counter_matches_jax_trips():
    with jax.enable_x64():
        jax_trips = _trips("jax", "greedy")
    np_trips = _trips("numpy", "greedy")
    assert set(np_trips) == {"bisection"}
    assert np_trips["bisection"] == jax_trips["bisection"] > 0
