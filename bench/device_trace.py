"""Device time from the JAX profiler's trace.

A traced run profiles one stretch of its measured window.  The benchmark
marks the stretch, each round and each call into the program with host
annotations (``bench.*``); the chip's ``XLA Modules`` line gives one event
per program execution.  From those:

* busy: the union of the program executions inside the stretch;
* per program: the summed execution time of each program, by the name JAX
  gives it (``jit__partition_units_impl``, ``jit__fold_in_impl``, ...);
* idle gaps: the stretch less busy, each gap named by the innermost
  benchmark annotation that holds its midpoint (what the host was doing).

All times are nanoseconds on the trace's own clock, which host and device
planes share.
"""

from __future__ import annotations

import glob
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import jax

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
STRETCH = "bench.traced"
PREFIX = "bench."

Event = Tuple[str, float, float]  # name, start ns, end ns


def options():
    """Profiler options: no Python call tracing (it would fill the trace
    with the interpreter's own calls), host annotations kept."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def program_name(module: str) -> str:
    """``jit__fold_in_impl(7404394923389019771)`` -> ``jit__fold_in_impl``."""
    return module.split("(", 1)[0]


def load(trace_dir: str) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """Program executions per device plane, and the benchmark's host
    annotations, from the one ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one profiler trace under {trace_dir}, found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    devices[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    ]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(PREFIX)
                )
    return devices, host


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def label_at(host: Sequence[Event], t: float) -> str:
    """The innermost (shortest) host annotation holding ``t``."""
    best: Optional[Event] = None
    for ev in host:
        if ev[0] != STRETCH and ev[1] <= t < ev[2]:
            if best is None or ev[2] - ev[1] < best[2] - best[1]:
                best = ev
    return best[0] if best is not None else "none"


def reduce(devices: Dict[str, List[Event]], host: List[Event]) -> Optional[dict]:
    """Busy, idle and per-program seconds inside the traced stretch,
    averaged over the device planes; ``None`` without a stretch or a device
    event in it."""
    stretch = [ev for ev in host if ev[0] == STRETCH]
    if len(stretch) != 1 or not devices:
        return None
    _, w0, w1 = stretch[0]
    rounds = sum(1 for ev in host if ev[0] == "bench.round" and w0 <= ev[2] <= w1)
    busy_ns = 0.0
    programs: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for events in devices.values():
        clipped = [
            (name, max(a, w0), min(b, w1)) for name, a, b in events if b > w0 and a < w1
        ]
        for name, a, b in clipped:
            programs[program_name(name)] += (b - a) / len(devices)
        spans = union([(a, b) for _, a, b in clipped])
        busy_ns += sum(b - a for a, b in spans) / len(devices)
        edge = w0
        for a, b in spans + [(w1, w1)]:
            if a > edge:
                gaps[label_at(host, 0.5 * (edge + a))] += (a - edge) / len(devices)
            edge = max(edge, b)
    if busy_ns <= 0.0:
        return None
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "rounds": rounds,
        "programs_s": {k: v * 1e-9 for k, v in programs.items()},
        "idle_s": {k: v * 1e-9 for k, v in gaps.items()},
    }


def breakdown(red: dict) -> dict:
    """The ten programs with the most device time and the ten host
    activities with the most device idle time under them."""
    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"device_ops": top(red["programs_s"]), "idle_gaps": top(red["idle_s"])}
