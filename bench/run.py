"""Run one cell of the scheduler benchmark once, on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``, whose ``"truth"`` names
``bench/truth/<truth>.py``) and a traffic mix (``bench/traffic/<traffic>.json``,
whose ``"loop"`` names ``bench/loops/<loop>.py``); the loop runs the mix's
closed scheduling rounds against the program's facade.  Set-up builds the
truth, warms every program the window will run and counts as ``setup_s``;
the window then runs rounds for ``--seconds``.  Afterwards the record of
what the program served is replayed through the plain reference
(``bench/reference.py``) and compared under the limits of
``bench/limits/<workload>.json``.

The last line on standard output is the result: ``correct``, ``attempted``
(rounds), ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, each read by
``bench/metrics/<metric>.py``), ``device``, ``breakdown`` (traced runs) and
``checks``, each compared number beside its limit; the same numbers are the
last lines on standard error.  Exits 2, printing no result, off the TPU or
with fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


def load_cell(workload: str):
    """``(spec, cell, config, mix, limits)`` for one workload name."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())
    return spec, cell, config, mix, limits


def metrics_for(spec: dict, cell: str, kind: str) -> list:
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


def configure_jax():
    """float64 estimates, and the persistent compilation cache at one fixed
    path inside the checkout, set before the backend starts."""
    import jax

    jax.config.update("jax_enable_x64", True)
    CACHE_DIR.mkdir(exist_ok=True)  # JAX writes no entry into a missing directory
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no eviction: it reads an access-time file beside every entry, and a
    # directory restored without them would fail every write
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             control: bool = False, overrides: dict = None, t_start: float = None):
    """Run one cell in this process; returns ``(result, check_lines,
    info_lines)``.  ``control`` runs the program's float32 path (the
    lower-precision control that the comparison must reject); ``overrides``
    patches the configuration and the mix (tests run tiny sizes)."""
    jax = configure_jax()

    from bench import device_trace
    from bench.compile_events import compile_window, listen
    from bench.harness import Tracer, load

    t_start = T_START if t_start is None else t_start
    listen()
    spec, cell, config, mix, limits = load_cell(workload)
    overrides = overrides or {}
    config.update(overrides.get("config", {}))
    mix.update(overrides.get("mix", {}))
    seed = int(seed) % (1 << 63)

    tracer = Tracer(trace, float(mix["trace_after_s"]), float(mix["trace_seconds"]))
    tmp = tempfile.TemporaryDirectory() if trace else None
    tracer.dir = tmp.name if trace else None
    tel = None
    if trace:
        from repro import obs

        tel = obs.Telemetry()
        obs.install(tel)
    try:
        t_jax = time.perf_counter()
        with compile_window() as cw_setup:
            loop = load("loops", mix["loop"]).Loop(config, mix, seed, control=control,
                                                   tracer=tracer)
            t_truth = time.perf_counter()
            loop.setup()
        with compile_window() as cw:
            w = loop.run(seconds)
    finally:
        if trace:
            obs.uninstall()
    devices = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    setup_s = w.t0 - t_start
    window_s = w.t1 - w.t0
    lat = np.asarray([r.t1 - r.t0 for r in w.rounds])
    tuned = [s.tuned[1] - s.tuned[0] for s in w.sessions
             if s.tuned is not None and s.tuned[0] >= w.t0 and s.converged]
    unconverged = sum(1 for s in w.sessions if s.tuned is not None and not s.converged)
    e2e = {
        "round_ms": 1e3 * window_s / len(lat) if len(lat) else None,
        "round_p95_ms": 1e3 * float(np.percentile(lat, 95)) if len(lat) else None,
        "tune_s": sum(tuned) / len(tuned) if tuned else None,
        "setup_s": setup_s,
    }
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    metrics, extra = {}, {}
    if trace:
        red = device_trace.reduce(*device_trace.load(tracer.dir))
        tmp.cleanup()
        spans = {}
        for ev in tel.events:
            if ev.kind == "span" and ev.t0 >= w.t0 and ev.t1 <= w.t1:
                spans.setdefault(ev.name, []).append((ev.t0, ev.t1))
        view = SimpleNamespace(
            t0=w.t0, t1=w.t1, rounds=w.rounds, sessions=w.sessions, spans=spans,
            device=red, compiles=cw.stats["compiles"],
            rounds_traced=sum(1 for r in w.rounds
                              if tracer.t0 is not None and tracer.t0 <= r.t1 <= tracer.t1),
        )
        for m in metrics_for(spec, workload, "per_layer"):
            v = load("metrics", m["name"]).read(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
            extra["breakdown"] = device_trace.breakdown(red)
    else:
        for m in metrics_for(spec, workload, "end_to_end"):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    info = [
        {"info": "setup", "setup_s": setup_s, "start_s": t_jax - t_start,
         "truth_s": t_truth - t_jax, "warm_s": w.t0 - t_truth,
         **{f"setup_{k}": v for k, v in cw_setup.stats.items()}},
        {"info": "window", "window_s": window_s, "rounds": len(lat),
         "generator_s": w.gen_s, "generator_share": w.gen_s / window_s,
         "window_compiles": cw.stats["compiles"], "window_cache_hits": cw.stats["cache_hits"]},
    ]

    # the comparison runs once the window is closed and the program's live
    # state is dropped; its time counts in neither setup_s nor the window
    t_check = time.perf_counter()
    loop.release()
    gc.collect()
    checks = loop.check(np.random.default_rng([seed, 1]))
    info.append({"info": "check", "check_s": time.perf_counter() - t_check})
    missing = sorted(set(checks) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing} in bench/limits/{workload}.json")
    correct = all(checks[k] <= limits[k] for k in checks)
    result = {
        "correct": bool(correct),
        "attempted": int(len(lat)),
        "failed": int(checks.get("guarantee_breaks", 0) + unconverged),
        "metrics": metrics,
        "device": device,
        **extra,
        "checks": {k: {"value": checks[k], "limit": limits[k]} for k in checks},
    }
    lines = [f"check {k} {checks[k]!r} limit {limits[k]!r}" for k in checks]
    return result, lines, info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    _, cell, _, _, _ = load_cell(args.workload)
    devices = configure_jax().devices()
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    result, lines, info = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    for line in info:
        print(json.dumps(line), flush=True)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
