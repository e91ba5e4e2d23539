"""Drive the scheduler's device path once on a TPU, at cluster scale.

    python3 chip_smoke.py              # phases 1-3 on one chip
    python3 chip_smoke.py --four-chip  # phase 4 only, on a four-chip host

Phases, all in this one process (a chip belongs to one process at a time):

1. one job, flat, p=10^4 processors, n=10^6 units: ``Scheduler(backend=
   "jax")`` autotunes against the seeded ``SimulatedExecutor`` cost model,
   then serves a few ``observe`` rounds with the fold-in carry donated.  The
   first partition is compared with the numpy bank's on the same estimates.
2. a fleet, q=16 tenants x p=10^3: ``FleetScheduler(backend="jax")``
   measurement rounds and three rebalance rounds, sync and pipelined, each
   compared with q independent numpy ``Scheduler.autotune`` loops.
3. the paper's kernel: ``matmul_update`` through Pallas at 4096^3 bf16,
   compared with the jnp reference.
4. (``--four-chip`` only) the p=10^6 two-level hierarchy, eight groups of
   125 000 processors sharded over four chips with ``shard_map``, compared
   with the unsharded hierarchy on one chip.

Each phase prints one JSON line: its sizes, compile and steady seconds
(host clock around work that ends in a host read) and the numbers it was
compared on.  Any failed comparison raises and the script exits non-zero;
so does a platform other than ``tpu``.  The last line on success is
``{"ok": true, "device": {...}}``.  Compiled programs are kept in the
persistent cache (``repro.compile_cache``), so a second run in the same
checkout compiles less.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.fleet_scale import make_tenants  # noqa: E402
from benchmarks.partition_scale import make_fleet, make_fleet_bank  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    BatchedSimulatedExecutor2D,
    Hierarchy,
    PiecewiseLinearFPM,
    Policy,
    Scheduler,
    SimulatedExecutor,
    SpeedStore,
)
from repro import obs  # noqa: E402
from repro.fleet import FleetScheduler, JobSpec  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402


class SmokeFailure(AssertionError):
    """A phase's result is outside its stated tolerance."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# -- compile accounting -------------------------------------------------------

_COMPILE = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}
_LISTENING = False


def _listen() -> None:
    """Count compile seconds and persistent-cache hits through JAX's own
    monitoring events (registered once per process)."""
    global _LISTENING
    if _LISTENING:
        return

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILE["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _COMPILE["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _LISTENING = True


class compile_window:
    """Compile seconds and cache hits/misses spent inside a ``with`` block."""

    def __enter__(self):
        _listen()
        self.start = dict(_COMPILE)
        return self

    def __exit__(self, *exc):
        self.stats = {k: _COMPILE[k] - self.start[k] for k in _COMPILE}
        return False


def report(phase: str, **fields) -> dict:
    line = {"phase": phase, **fields}
    print(json.dumps(line), flush=True)
    return line


def _max_diff(a, b) -> int:
    return int(np.max(np.abs(np.asarray(a, np.int64) - np.asarray(b, np.int64))))


def _check_alloc(d, n: int, floor: int, cap, what: str) -> None:
    d = np.asarray(d, np.int64)
    check(int(d.sum()) == n, f"{what}: sum {int(d.sum())} != n {n}")
    check(int(d.min()) >= floor, f"{what}: allocation {int(d.min())} below floor {floor}")
    check(bool(np.all(d <= np.asarray(cap))), f"{what}: allocation above its cap")


# -- phase 1: one job, flat ---------------------------------------------------


def phase_flat(p=10_000, n=1_000_000, *, eps=0.05, max_iter=12, serve_rounds=3,
               seed=0) -> dict:
    truth = make_fleet(p, seed=seed)
    caps = [4 * n // p] * p

    def session(backend):
        sched = Scheduler(policy=Policy.DFPA, backend=backend, eps=eps,
                          min_units=1, caps=caps)
        ex = SimulatedExecutor(time_fns=[m.time for m in truth])
        t0 = time.perf_counter()
        res = sched.autotune(ex, n, eps, max_iter=max_iter, min_units=1)
        return sched, res, time.perf_counter() - t0

    with compile_window() as cw:
        sched_j, res_j, tune_s = session("jax")
    sched_n, res_n, _ = session("numpy")

    # The first partition computed from estimates (round 2's distribution):
    # both sessions measured the same even split, so their stores agree.
    first_j = res_j.diagnostics["history"][1][0]
    first_n = res_n.diagnostics["history"][1][0]
    first_diff = _max_diff(first_j, first_n)
    check(first_diff <= 1, f"flat: first partition differs from numpy by {first_diff} units")
    for res, name in ((res_j, "jax"), (res_n, "numpy")):
        for r, (d, _) in enumerate(res.diagnostics["history"]):
            _check_alloc(d, n, 1, caps, f"flat {name} round {r}")
        check(res.converged and res.imbalance <= eps,
              f"flat {name}: imbalance {res.imbalance} > eps {eps} "
              f"after {res.iterations} rounds")

    steady = []
    for _ in range(3):
        t0 = time.perf_counter()
        d = sched_j.store.partition_units(n, caps, min_units=1)
        steady.append(time.perf_counter() - t0)
    _check_alloc(d, n, 1, caps, "flat steady repartition")

    # Serving: a tenth of the processors slow down by half; observe() folds
    # each round's times into the donated carry and repartitions.
    slow = np.arange(p) % 10 == 0

    def drifted(i):
        m = truth[i]
        return (lambda x: 1.5 * m.time(x)) if slow[i] else m.time

    ex_serve = SimulatedExecutor(time_fns=[drifted(i) for i in range(p)])
    carry = sched_j.store.device_bank(snapshot=False)
    observe_s, moved = [], 0
    for r in range(serve_rounds):
        times = ex_serve.run(sched_j.d)
        t0 = time.perf_counter()
        moved += bool(sched_j.observe(times))
        observe_s.append(time.perf_counter() - t0)
        sched_n.observe(ex_serve.run(sched_n.d))
        _check_alloc(sched_j.d, n, 1, caps, f"flat observe round {r}")
    check(carry.counts.is_deleted(), "flat: the folded carry was not donated")
    check(moved > 0, "flat: no observe round repartitioned")
    return report(
        "flat", p=p, n=n, eps=eps,
        rounds_jax=res_j.iterations, rounds_numpy=res_n.iterations,
        imbalance_jax=res_j.imbalance, imbalance_numpy=res_n.imbalance,
        first_partition_max_unit_diff=first_diff,
        autotune_s=tune_s, compile_s=cw.stats["compile_s"],
        cache_hits=cw.stats["cache_hits"], cache_misses=cw.stats["cache_misses"],
        steady_partition_s=min(steady),
        observe_s=observe_s, observe_repartitions=moved,
        serve_max_unit_diff_vs_numpy=_max_diff(sched_j.d, sched_n.d),
        carry_donated=True,
    )


# -- phase 2: a fleet ---------------------------------------------------------


def phase_fleet(q=16, p=1_000, *, eps=0.05, max_iter=10, rebalance_rounds=3,
                seed=0) -> dict:
    _, warm, base, knee = make_tenants(q, p, seed=seed)
    names = [f"t{j}" for j in range(q)]
    # About 3000 units per processor puts the tenants at their memory knees
    # (2e3..2e4 units), where the 6-point warm profiles interpolate the time
    # curve loosely: round 0 is a real partition, and every tenant measures
    # a few rounds before it converges.
    ns = [3000 * p + 7 * j for j in range(q)]

    def time_fn(X):
        return X * base * (1.0 + np.where(X > knee, 3.0 * (X - knee) / knee, 0.0))

    def proc_fn(b, k):
        return lambda x: float(x * b * (1.0 + (3.0 * (x - k) / k if x > k else 0.0)))

    indep = []
    for j in range(q):
        store = SpeedStore.from_models(
            [PiecewiseLinearFPM.from_points(m.as_points()) for m in warm[j]],
            backend="numpy",
        )
        ex = SimulatedExecutor(time_fns=[proc_fn(base[j, i], knee[j, i]) for i in range(p)])
        indep.append(Scheduler(store, backend="numpy").autotune(
            ex, ns[j], eps, max_iter=max_iter, min_units=1))
    for j, res in enumerate(indep):
        check(res.converged, f"fleet: numpy tenant {names[j]} did not converge")

    out = {}
    for mode, pipeline in (("sync", False), ("pipeline", True)):
        fleet = FleetScheduler(p, backend="jax", pipeline=pipeline, pipeline_depth=1)
        for j in range(q):
            fleet.admit(JobSpec(name=names[j], n=ns[j], eps=eps, min_units=1,
                                max_iter=max_iter), models=warm[j])
        ex = BatchedSimulatedExecutor2D(time_fn_batch_2d=time_fn, p=p, q=q,
                                        job_names=names)
        with compile_window() as cw:
            t0 = time.perf_counter()
            results = fleet.run(ex)
            run_s = time.perf_counter() - t0
        round0 = 0
        for j, nm in enumerate(names):
            res = results[nm]
            check(res.converged, f"fleet {mode}: tenant {nm} did not converge")
            for r, (d, _) in enumerate(res.diagnostics["history"]):
                _check_alloc(d, ns[j], 1, [ns[j]] * p, f"fleet {mode} {nm} round {r}")
            round0 = max(round0, _max_diff(res.diagnostics["history"][0][0],
                                           indep[j].diagnostics["history"][0][0]))
        check(round0 <= 1, f"fleet {mode}: round-0 allocations differ from numpy "
                           f"by {round0} units")

        # Serving: tenant loads drift; one stacked program per round, read
        # from the newest carry (drain() completes a pipeline's last fold).
        fleet.drain()
        rebalance_s, serve_diff = [], 0
        tel = obs.Telemetry()  # its jax.compile spans name what compiled
        for r in range(rebalance_rounds):
            loads = {nm: ns[j] + (r * 29 + j * 13) % 97 for j, nm in enumerate(names)}
            t0 = time.perf_counter()
            with obs.use(tel):
                ds = fleet.rebalance(loads)
            rebalance_s.append(time.perf_counter() - t0)
            for nm, load in loads.items():
                _check_alloc(ds[nm], load, 1, [load] * p, f"fleet {mode} rebalance {nm}")
            nm = names[r % q]
            want = SpeedStore.from_models(fleet.models(nm), backend="numpy") \
                .partition_units(loads[nm], min_units=1)
            serve_diff = max(serve_diff, _max_diff(ds[nm], want))
        check(serve_diff <= 1, f"fleet {mode}: rebalance differs from numpy by "
                               f"{serve_diff} units")
        compiled = [e.attrs["fun_name"] for e in tel.spans(obs.COMPILE_SPAN)]
        out[mode] = report(
            f"fleet_{mode}", q=q, p=p, eps=eps, rounds=fleet.rounds,
            device_dispatches=fleet.device_dispatches,
            max_iterations=max(results[nm].iterations for nm in names),
            round0_max_unit_diff=round0, rebalance_max_unit_diff=serve_diff,
            run_s=run_s, compile_s=cw.stats["compile_s"],
            cache_hits=cw.stats["cache_hits"], cache_misses=cw.stats["cache_misses"],
            rebalance_s=rebalance_s,
            rebalance_new_compiles={
                "partition": sum("_partition_units_impl" in f for f in compiled),
                "fold": sum("_fold_in_impl" in f for f in compiled),
            },
        )
    return out


# -- phase 3: the paper's kernel ----------------------------------------------


def phase_kernel(m=4096, k=4096, n=4096, *, dtype=jnp.bfloat16, impl="pallas",
                 atol=5e-2, rtol=2e-2, seed=0) -> dict:
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = jax.random.normal(k1, (m, k), jnp.float32).astype(dtype)
    b = jax.random.normal(k2, (k, n), jnp.float32).astype(dtype)
    c = jax.random.normal(k3, (m, n), jnp.float32).astype(dtype)
    want = np.asarray(ref.matmul_update_ref(c, a, b), np.float32)
    with compile_window() as cw:
        t0 = time.perf_counter()
        out = ops.matmul_update(c, a, b, impl=impl).block_until_ready()
        first_s = time.perf_counter() - t0
    steady = []
    for _ in range(3):
        t0 = time.perf_counter()
        ops.matmul_update(c, a, b, impl=impl).block_until_ready()
        steady.append(time.perf_counter() - t0)
    got = np.asarray(out, np.float32)
    err = np.abs(got - want)
    bound = atol * np.sqrt(k) + rtol * np.abs(want)
    check(got.shape == (m, n) and bool(np.all(np.isfinite(got))),
          "kernel: output not finite or of the wrong shape")
    check(bool(np.all(err <= bound)),
          f"kernel: {int(np.sum(err > bound))} elements outside atol/rtol")
    return report(
        "kernel", m=m, k=k, n=n, dtype=jnp.dtype(dtype).name, impl=impl,
        max_abs_err=float(err.max()), atol=atol * float(np.sqrt(k)), rtol=rtol,
        first_call_s=first_s, compile_s=cw.stats["compile_s"],
        cache_hits=cw.stats["cache_hits"], steady_s=min(steady),
    )


# -- phase 4: the p=10^6 hierarchy across chips -------------------------------


def phase_hierarchy_sharded(g=8, p_g=125_000, *, ndev=4, seed=1000) -> dict:
    check(len(jax.devices()) >= ndev,
          f"hierarchy: needs {ndev} devices, JAX has {len(jax.devices())}")
    banks = [make_fleet_bank(p_g, seed=seed + i) for i in range(g)]
    p = g * p_g
    n = 20 * p
    caps = np.full(p, n, dtype=np.int64)
    flat_elems = 2 * p * int(banks[0].xs.shape[1])

    h_shard = Hierarchy.from_group_banks(banks, backend="jax", sharding="shard_map")
    h_one = Hierarchy.from_group_banks(banks, backend="jax")
    with compile_window() as cw:
        t0 = time.perf_counter()
        d_shard = h_shard.partition_units(n, caps, min_units=1)
        first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    d_shard = h_shard.partition_units(n, caps, min_units=1)
    steady_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    d_one = h_one.partition_units(n, caps, min_units=1)
    one_first_s = time.perf_counter() - t0

    shard_elems = h_shard.max_shard_elems()
    one_elems = h_one.max_shard_elems()
    diff = _max_diff(d_shard, d_one)
    check(len(h_shard.inner_devices) == ndev,
          f"hierarchy: sharded inner solve ran on {len(h_shard.inner_devices)} "
          f"devices, not {ndev}")
    check(len(h_one.inner_devices) == 1, "hierarchy: unsharded solve left one device")
    check(shard_elems * ndev <= flat_elems,
          f"hierarchy: a device holds {shard_elems} bank elements, more than "
          f"1/{ndev} of the flat {flat_elems}")
    _check_alloc(d_shard, n, 1, caps, "hierarchy sharded")
    _check_alloc(d_one, n, 1, caps, "hierarchy one chip")
    check(diff <= 1, f"hierarchy: sharded differs from one chip by {diff} units")
    return report(
        "hierarchy_sharded", p=p, g=g, n=n, devices=len(h_shard.inner_devices),
        max_shard_elems=shard_elems, one_chip_elems=one_elems,
        flat_bank_elems=flat_elems, max_unit_diff=diff,
        first_call_s=first_s, compile_s=cw.stats["compile_s"],
        steady_s=steady_s, one_chip_first_call_s=one_first_s,
    )


# -- entry point --------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded p=10^6 hierarchy on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    jax.config.update("jax_enable_x64", True)
    report("setup", compile_cache=cache_dir, devices=len(devices),
           kind=devices[0].device_kind)

    if args.four_chip:
        phase_hierarchy_sharded()
    else:
        phase_flat()
        phase_fleet()
        phase_kernel()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
