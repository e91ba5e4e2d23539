"""The telemetry sink: spans, counters, gauges, events — and the no-op.

Design constraints (they shape every API choice here):

* **Near-zero cost when disabled.**  The scheduler stack is instrumented on
  its hot paths (every fleet round, every fold, every bisection).  All
  instrumentation sites follow one pattern::

      tel = _obs_active()
      rec = tel is not None and tel.enabled
      if rec:
          tok = tel.begin("layer.step", n=n)
      ...work...
      if rec:
          tel.end(tok)

  so a disabled build executes two attribute checks and nothing else — no
  allocation, no call into this module (``tests/test_obs.py`` locks this
  with a counting stub sink).  ``span()`` is the same pair as a context
  manager, for cold paths (examples, harnesses).

* **One span mechanism, on the profiler's clock.**  ``begin`` records the
  span open at that moment as the new span's ``parent`` (so every span of a
  round reaches the round's root, whose attrs identify it) and holds a
  ``jax.profiler.TraceAnnotation`` until ``end``: with a profiler running,
  the program's spans land on its host plane beside the device events.
  With none running an annotation costs about a microsecond.  ``span_at``
  (explicit endpoints) is for simulated time axes only.

* **Never on device paths.**  Telemetry records host-side bookkeeping only;
  no instrumentation site touches arrays bound for a device program, so
  results hold bit-identically with telemetry on or off.

* **Process-global, import-optional.**  The active sink is a module global
  (``active()`` / ``install()``); instrumented modules import it inside a
  ``try`` so the whole ``repro.obs`` package can be absent (or stubbed by a
  test) without changing scheduler behaviour.  Installing the first enabled
  sink registers one ``jax.monitoring`` listener that turns every backend
  compile into a ``jax.compile`` span (attr ``fun_name``) under the span
  open at the time.

* **Injectable clock.**  ``Telemetry(clock=...)`` makes traces deterministic
  in tests and lets harnesses record on a simulated time axis.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = [
    "Event",
    "Telemetry",
    "NoopTelemetry",
    "NOOP",
    "COMPILE_SPAN",
    "active",
    "install",
    "uninstall",
    "use",
]

COMPILE_SPAN = "jax.compile"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Event(NamedTuple):
    """One recorded fact.  ``kind`` is ``"span"`` (t0 < t1), ``"counter"``
    (value = increment), ``"gauge"`` (value = level) or ``"event"`` (a point
    occurrence); ``attrs`` carries site-specific context (JSON-safe).
    ``id`` numbers spans from 1 (0 for the other kinds); ``parent`` is the
    id of the span open when the fact was recorded (0: none)."""

    kind: str
    name: str
    t0: float
    t1: float
    value: float
    attrs: Dict[str, Any]
    id: int = 0
    parent: int = 0

    def to_dict(self) -> Dict[str, Any]:
        return {
            "kind": self.kind,
            "name": self.name,
            "t0": self.t0,
            "t1": self.t1,
            "value": self.value,
            "attrs": dict(self.attrs),
            "id": self.id,
            "parent": self.parent,
        }


class _Open:
    """An open span: what ``begin`` hands back and ``end`` closes."""

    __slots__ = ("id", "parent", "name", "attrs", "ann", "t0")

    def __init__(self, id: int, parent: int, name: str, attrs: Dict[str, Any]):
        self.id = id
        self.parent = parent
        self.name = name
        self.attrs = attrs


class _Span:
    """Context-manager span (cold paths): ``begin`` on entry, ``end`` on
    exit."""

    __slots__ = ("_tel", "_name", "_attrs", "_tok")

    def __init__(self, tel: "Telemetry", name: str, attrs: Dict[str, Any]):
        self._tel = tel
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> "_Span":
        self._tok = self._tel.begin(self._name, **self._attrs)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._tel.end(self._tok)
        return False


class _NoopSpan:
    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NOOP_SPAN = _NoopSpan()
_EMPTY: Dict[str, Any] = {}


class Telemetry:
    """A recording sink.

    ``capacity`` bounds the event buffer (a ring: oldest events drop) —
    the flight recorder builds on this; ``None`` keeps everything.
    ``counters`` accumulate (name -> running total) and ``gauges`` hold the
    last written level, independent of the ring, so a bounded recorder
    still reports whole-run totals.
    """

    enabled: bool = True

    def __init__(
        self,
        *,
        clock: Callable[[], float] = time.perf_counter,
        capacity: Optional[int] = None,
    ):
        self.clock = clock
        self.capacity = capacity
        self.events: Any = (
            [] if capacity is None else deque(maxlen=int(capacity))
        )
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self._last_id = 0
        self._open = 0  # id of the innermost open span

    # -- recording ------------------------------------------------------------

    def begin(self, name: str, **attrs: Any) -> _Open:
        """Open a span under the one open now; close it with :meth:`end`."""
        self._last_id += 1
        tok = _Open(self._last_id, self._open, name, attrs)
        self._open = tok.id
        tok.ann = TraceAnnotation(name)
        tok.ann.__enter__()
        tok.t0 = self.clock()
        return tok

    def end(self, tok: _Open, **attrs: Any) -> None:
        """Close ``tok`` and record it; ``attrs`` join those given to
        ``begin``.  The span open before ``tok`` is open again."""
        t1 = self.clock()
        tok.ann.__exit__(None, None, None)
        self._open = tok.parent
        if attrs:
            attrs = {**tok.attrs, **attrs}
        else:
            attrs = tok.attrs or _EMPTY
        self.events.append(
            Event("span", tok.name, tok.t0, t1, t1 - tok.t0, attrs, tok.id, tok.parent)
        )

    def span(self, name: str, **attrs: Any) -> _Span:
        """``with tel.span("repartition"): ...`` — records one span event."""
        return _Span(self, name, attrs)

    def span_at(self, name: str, t0: float, t1: float, **attrs: Any) -> None:
        """Record a span with explicit endpoints (simulated time axes), under
        the span open now."""
        self._last_id += 1
        self.events.append(Event("span", name, t0, t1, t1 - t0, attrs or _EMPTY,
                                 self._last_id, self._open))

    def counter(self, name: str, value: float = 1, **attrs: Any) -> None:
        self.counters[name] = self.counters.get(name, 0) + value
        t = self.clock()
        self.events.append(Event("counter", name, t, t, value, attrs or _EMPTY, 0, self._open))

    def gauge(self, name: str, value: float, **attrs: Any) -> None:
        self.gauges[name] = value
        t = self.clock()
        self.events.append(Event("gauge", name, t, t, value, attrs or _EMPTY, 0, self._open))

    def event(self, name: str, **attrs: Any) -> None:
        t = self.clock()
        self.events.append(Event("event", name, t, t, 1.0, attrs or _EMPTY, 0, self._open))

    # -- introspection --------------------------------------------------------

    def spans(self, name: Optional[str] = None) -> List[Event]:
        return [
            e for e in self.events
            if e.kind == "span" and (name is None or e.name == name)
        ]

    def to_payload(self) -> Dict[str, Any]:
        """JSON-safe dump: the (possibly ring-bounded) events plus the
        unbounded counter totals and last gauge levels."""
        return {
            "events": [e.to_dict() for e in self.events],
            "counters": dict(self.counters),
            "gauges": dict(self.gauges),
        }

    def clear(self) -> None:
        self.events.clear()
        self.counters.clear()
        self.gauges.clear()


class NoopTelemetry:
    """The disabled default.  ``enabled`` is False so guarded call sites
    skip it entirely; the methods still exist (and do nothing) so an
    unguarded call is safe."""

    enabled: bool = False
    clock = staticmethod(time.perf_counter)

    def begin(self, name: str, **attrs: Any) -> None:
        return None

    def end(self, tok: Any, **attrs: Any) -> None:
        return None

    def span(self, name: str, **attrs: Any) -> _NoopSpan:
        return _NOOP_SPAN

    def span_at(self, name: str, t0: float, t1: float, **attrs: Any) -> None:
        return None

    def counter(self, name: str, value: float = 1, **attrs: Any) -> None:
        return None

    def gauge(self, name: str, value: float, **attrs: Any) -> None:
        return None

    def event(self, name: str, **attrs: Any) -> None:
        return None


NOOP = NoopTelemetry()
_ACTIVE: Any = NOOP


def active() -> Any:
    """The process-global sink every instrumentation site consults."""
    return _ACTIVE


def _on_compile(event: str, secs: float, **kw: Any) -> None:
    """``jax.monitoring`` duration listener: a backend compile (or a
    persistent-cache load, which fires the same event) becomes a
    ``jax.compile`` span ending now, under the span open now."""
    tel = _ACTIVE
    if event != _COMPILE_EVENT or not tel.enabled:
        return
    t1 = tel.clock()
    tel.span_at(COMPILE_SPAN, t1 - secs, t1, fun_name=str(kw.get("fun_name", "?")))


_LISTENING = False


def install(tel: Optional[Any]) -> Any:
    """Make ``tel`` the process-global sink; returns the previous one.
    ``install(None)`` restores the no-op.  The first enabled sink registers
    the compile listener (once per process)."""
    global _ACTIVE, _LISTENING
    prev = _ACTIVE
    _ACTIVE = tel if tel is not None else NOOP
    if not _LISTENING and _ACTIVE.enabled:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _LISTENING = True
    return prev


def uninstall() -> None:
    install(None)


class use:
    """``with use(tel): ...`` — scoped install/restore."""

    def __init__(self, tel: Optional[Any]):
        self._tel = tel
        self._prev: Any = None

    def __enter__(self) -> Any:
        self._prev = install(self._tel)
        return self._tel

    def __exit__(self, exc_type, exc, tb) -> bool:
        install(self._prev)
        return False
