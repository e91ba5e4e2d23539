"""Training CLI: real steps on the local device set, DFPA-balanced groups.

Two modes:
  * ``--groups 1`` (default): plain single-group training of a (reduced)
    config — the end-to-end driver used by examples/quickstart.
  * ``--groups N``: heterogeneous multi-group training; each group runs its
    own jit'd accumulation step over its DFPA-allocated units.  On this
    CPU container groups share one device, so per-group heterogeneity is
    emulated by a configurable slowdown factor applied to the *measured*
    step time (the control plane — DFPA, straggler detection, elastic
    rebalancing — is exercised for real).

Usage:
    python -m repro.launch.train --arch gemma2-2b --smoke --steps 20
    python -m repro.launch.train --arch xlstm-350m --smoke --groups 4 \
        --hetero 1.0,1.4,2.0,3.1 --steps 12
"""

from __future__ import annotations

import argparse
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..compile_cache import use_compile_cache
from ..configs import ARCH_IDS, get_config, get_smoke_config
from ..checkpoint import CheckpointManager, load_checkpoint
from ..core.scheduler import Scheduler
from ..data import SyntheticLMData, UnitBatcher
from ..optim.schedule import warmup_cosine
from ..runtime.straggler import StragglerAction, StragglerDetector
from ..runtime.train_loop import init_train_state, make_train_step

__all__ = ["main"]


def _host_batch(cfg, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
    return {k: jnp.asarray(v) for k, v in batch.items()}


def train_single(cfg, *, steps: int, batch: int, seq: int, lr: float, ckpt_dir=None, log_every=1):
    key = jax.random.PRNGKey(0)
    state = init_train_state(cfg, key)
    step_fn = jax.jit(make_train_step(cfg, warmup_cosine(lr, max(steps // 10, 1), steps)))
    data = SyntheticLMData(cfg, batch, seq)
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    losses = []
    for i in range(steps):
        b = _host_batch(cfg, data.next())
        t0 = time.perf_counter()
        state, metrics = step_fn(state, b)
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        losses.append(loss)
        if i % log_every == 0:
            print(f"step {i:4d} loss {loss:8.4f} gnorm {float(metrics['grad_norm']):7.3f} {dt*1e3:7.1f}ms", flush=True)
        if mgr and (i + 1) % 50 == 0:
            mgr.save_async(i + 1, state, extra={"data": data.state_dict()})
    if mgr:
        mgr.save_async(steps, state)
        mgr.wait()
    return state, losses


def train_hetero(cfg, *, steps: int, groups: int, hetero: List[float], n_units: int,
                 micro_batch: int, seq: int, lr: float, eps: float = 0.15):
    """Multi-group DFPA-balanced training (per-group grad-accum steps)."""
    key = jax.random.PRNGKey(0)
    state = init_train_state(cfg, key)
    sched = warmup_cosine(lr, max(steps // 10, 1), steps)
    data = SyntheticLMData(cfg, micro_batch, seq)
    batcher = UnitBatcher(data, micro_batch)
    # One Scheduler session drives the whole control plane: online DFPA
    # observation, repartitioning, and straggler reprofiling.
    ctrl = Scheduler(
        n_units=n_units, num_groups=groups, eps=eps, min_units=1,
        detector=StragglerDetector(),
    )
    # One jit'd step per distinct accumulation length (shared cache).
    step_fns: Dict[int, object] = {}

    def step_for(a: int):
        if a not in step_fns:
            step_fns[a] = jax.jit(make_train_step(cfg, sched, accum_steps=a))
        return step_fns[a]

    print(f"groups={groups} hetero={hetero} units/step={n_units}")
    for i in range(steps):
        units = batcher.global_step_units(n_units, i)
        parts = batcher.split(units, ctrl.d)
        times, losses = [], []
        new_state = None
        for g, part in enumerate(parts):
            if ctrl.d[g] == 0:
                times.append(0.0)
                continue
            gb = {k: jnp.asarray(v) for k, v in part.items()}
            fn = step_for(ctrl.d[g])
            t0 = time.perf_counter()
            out_state, metrics = fn(state, gb)
            jax.block_until_ready(metrics["loss"])
            dt = (time.perf_counter() - t0) * hetero[g]  # emulated heterogeneity
            times.append(dt)
            losses.append(float(metrics["loss"]))
            if new_state is None:
                new_state = out_state  # groups' grads averaged in production;
                # single-device emulation keeps one group's update
        state = new_state
        # straggler scan BEFORE folding times into the models (REPROFILE
        # actions are applied by the facade automatically)
        acts = ctrl.straggler_actions(times)
        for g, act in enumerate(acts):
            if act is not StragglerAction.NONE:
                print(f"    straggler[{g}]: {act.value}", flush=True)
        changed = ctrl.observe(times)
        print(
            f"step {i:3d} loss {np.mean(losses):7.4f} times "
            + "/".join(f"{t*1e3:6.1f}" for t in times)
            + f" d={ctrl.d}{' (rebalanced)' if changed else ''}",
            flush=True,
        )
    print(f"rebalances: {ctrl.rebalances}, final d={ctrl.d}")
    return state, ctrl


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--groups", type=int, default=1)
    ap.add_argument("--hetero", default="", help="comma-separated slowdowns per group")
    ap.add_argument("--units", type=int, default=16, help="microbatches per global step")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.groups <= 1:
        train_single(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                     lr=args.lr, ckpt_dir=args.ckpt)
    else:
        het = [float(x) for x in args.hetero.split(",")] if args.hetero else [
            1.0 + 0.7 * g for g in range(args.groups)
        ]
        assert len(het) == args.groups
        train_hetero(cfg, steps=args.steps, groups=args.groups, hetero=het,
                     n_units=args.units, micro_batch=args.batch, seq=args.seq, lr=args.lr)


if __name__ == "__main__":
    main()
