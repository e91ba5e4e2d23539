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

    span = span_at = counter = gauge = event = _bump
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
