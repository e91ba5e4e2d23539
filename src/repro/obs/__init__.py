"""Observability for the scheduler stack: spans, counters, gauges, traces.

Observing the scheduler
=======================

The paper's central empirical claim is an observability claim: the cost of
distributing the computation (partial FPM estimation + repartitioning) is
orders of magnitude below the execution it optimizes.  This package is the
substrate that lets you *watch* that claim hold on a live session instead
of trusting one benchmark's printed fraction.

Everything is off by default — the process-global sink is a no-op and every
instrumentation site in the stack guards itself with a cheap ``enabled``
check, so an uninstrumented run is bit-identical and makes no call into this
package.  The enabled cost is measured in ``PERF.md`` (section 5) on the
chip benchmark's cells.  Turn it on by installing a sink::

    from repro import obs

    tel = obs.Telemetry()           # unbounded recording sink
    obs.install(tel)                # process-global: all layers now report
    ...  # run Scheduler / FleetScheduler / ReplicaDispatcher work
    obs.uninstall()                 # back to the no-op

    # scoped form
    with obs.use(obs.Telemetry()) as tel:
        fleet.step(executor)

Spans nest: each records the span open when it began as its ``parent``, and
each holds a ``jax.profiler.TraceAnnotation`` while open, so a profiler
trace shows them on its host plane beside the device programs.

What gets recorded (the instrumented layers):

* ``Scheduler`` — a flat round's root span ``scheduler.observe`` (attr
  ``step``) with children ``scheduler.ema`` (the EMA loop),
  ``speedstore.fold_in`` and, when it repartitions,
  ``speedstore.partition``; ``scheduler.autotune`` (the session) with one
  ``scheduler.tune_round`` root per measured round (attr ``iteration``)
  holding ``scheduler.measure`` (the executor's run), ``speedstore.fold_in``
  and ``speedstore.partition``; ``scheduler.partition`` spans and
  ``scheduler.reprofile`` events;
* ``SpeedStore`` — ``speedstore.fold_in`` with children
  ``speedstore.fold_host`` (list conversions, the scalar ``add_point``
  loop) and, on the jax backend, ``modelbank.fold_in``;
  ``speedstore.partition``, which on the host backends counts the
  bisection's steps as ``partition.trips.bisection``;
* ``JaxModelBank`` — ``modelbank.fold_in`` (uploads, width check, kernel
  dispatch) and ``modelbank.partition.wait`` (dispatch of the partition
  program until its results are on the host), after which the trips of each
  of the program's loops are counted as ``partition.trips.<loop>``
  (``doubling``, ``bisection``, ``takeback``, ``threshold_doubling``,
  ``threshold_bisection``, ``greedy``);
* JAX — every backend compile (or persistent-cache load) as a
  ``jax.compile`` span (attr ``fun_name``) under the span open at the time;
* ``FleetScheduler`` — the round lifecycle as spans (``fleet.round`` with
  nested ``fleet.partition`` / ``fleet.measure`` / ``fleet.fold``,
  ``fleet.rebalance``, ``fleet.observe``, ``fleet.power_cap``),
  restack/predispatch counters, speculation hit/miss/stale-read counters,
  the power-cap theta gauge, and every
  :meth:`~repro.fleet.scheduler.FleetScheduler.stats` field as a
  ``fleet.*`` gauge each round;
* ``Hierarchy`` — aggregation-cache hit/miss counters and outer/inner
  solve spans;
* ``StragglerDetector`` — ``straggler.strike`` events carrying the
  (predicted, observed, ratio) evidence and ``straggler.verdict`` events
  for REPROFILE/QUARANTINE;
* ``ReplicaDispatcher`` — per-epoch replica busy spans on per-replica
  tracks plus the live rebalance-vs-serve wall split (the paper's overhead
  ratio as a gauge);
* ``ProfileRegistry`` — every ``warnings.warn`` (missing/unreadable/
  malformed registry, staleness demotions) mirrored as a structured
  ``registry.warning`` event, so cold-start causes show up in traces.

Artifacts:

* :func:`~repro.obs.chrometrace.export_chrome_trace` writes a
  Chrome-trace/Perfetto JSON (open in ``chrome://tracing`` or
  https://ui.perfetto.dev) — fleet rounds as named spans on per-replica +
  scheduler tracks, so the PR 9 pipeline overlap is visible.  Wired as
  ``--trace out.json`` on ``benchmarks/serve_trace.py`` and
  ``benchmarks/fleet_scale.py``.
* :class:`~repro.obs.flightrec.FlightRecorder` — a ring-bounded sink plus
  estimate snapshots, dumped to JSON on QUARANTINE or gate failure for
  post-incident forensics without a rerun.
* ``python -m repro.obs.report trace.json`` — the paper-style summary
  table (overhead fraction, dispatches/round, compiles, speculation rates,
  reaction times) from either artifact.

See ``examples/obs_walkthrough.py`` for an end-to-end tour.
"""

from .chrometrace import export_chrome_trace, to_chrome_trace
from .flightrec import FlightRecorder
from .telemetry import (
    COMPILE_SPAN,
    NOOP,
    Event,
    NoopTelemetry,
    Telemetry,
    active,
    install,
    uninstall,
    use,
)

def __getattr__(name):
    # Lazy: ``python -m repro.obs.report`` executes report as __main__, and
    # an eager package-level import of the same module would make runpy warn
    # about the double life.
    if name == "MetricsSnapshot":
        from .report import MetricsSnapshot

        return MetricsSnapshot
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "COMPILE_SPAN",
    "Event",
    "Telemetry",
    "NoopTelemetry",
    "NOOP",
    "active",
    "install",
    "uninstall",
    "use",
    "FlightRecorder",
    "MetricsSnapshot",
    "export_chrome_trace",
    "to_chrome_trace",
]
