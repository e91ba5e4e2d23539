"""What every loop shares: the record of a window, the traced stretch, and
the loader that finds a loop, a truth or a metric reader by its name.

A loop lives in ``bench/loops/<name>.py`` and exports ``Loop``; a mix
(``bench/traffic/<mix>.json``) names it under ``"loop"``.  A truth lives in
``bench/truth/<name>.py`` and exports ``build(config, seed)``; a
configuration names it under ``"truth"``.  A per-layer metric lives in
``bench/metrics/<metric>.py`` and exports ``read(run)``.
"""

from __future__ import annotations

import contextlib
import importlib.util
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, List, Optional

BENCH = Path(__file__).resolve().parent
now = time.perf_counter


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    mod_name = f"bench_{kind}_" + "".join(c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Round:
    """One scheduling round: an observation in, an allocation on the host."""

    t0: float
    t1: float
    changed: bool


@dataclass
class Session:
    construct: tuple  # (t0, t1)
    tuned: Optional[tuple] = None  # (t0, t1) from construction to converged
    events: List[tuple] = field(default_factory=list)
    carry: Any = None
    converged: bool = False
    window_from: int = 0  # events before this index were set-up


@dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    rounds: List[Round] = field(default_factory=list)
    sessions: List[Session] = field(default_factory=list)
    gen_s: float = 0.0  # traffic generation inside the window


class Tracer:
    """Host annotations and the profiled stretch of a traced run (no-ops
    in an untraced run)."""

    def __init__(self, on: bool, start_after: float = 0.0, seconds: float = 0.0):
        self.on = on
        self.start_after = start_after
        self.seconds = seconds
        self.state = "before"
        self.dir = None
        self._ann = None
        self.t0 = self.t1 = None

    def annotate(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def tick(self, elapsed: float) -> None:
        """Start or stop the profiler at a round boundary."""
        if not self.on:
            return
        import jax

        from .device_trace import STRETCH, options

        if self.state == "before" and elapsed >= self.start_after:
            jax.profiler.start_trace(self.dir, profiler_options=options())
            self._ann = jax.profiler.TraceAnnotation(STRETCH)
            self._ann.__enter__()
            self.t0 = now()
            self.state = "tracing"
        elif self.state == "tracing" and elapsed >= self.start_after + self.seconds:
            self.stop()

    def stop(self) -> None:
        if self.state == "tracing":
            import jax

            self.t1 = now()
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"
