"""Serving CLI: batched prefill+decode with DFPA-balanced replica dispatch.

    python -m repro.launch.serve --arch gemma2-2b --smoke --batch 4 \
        --prompt-len 32 --new-tokens 16
    python -m repro.launch.serve --arch xlstm-350m --smoke --replicas 4 \
        --chunks 64   # DFPA dispatch demo across emulated replicas
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..compile_cache import use_compile_cache
from ..configs import ARCH_IDS, get_config, get_smoke_config
from ..nn.params import init_tree
from ..runtime.serve_loop import ReplicaDispatcher, ServeEngine
from ..runtime.train_loop import model_spec_for

__all__ = ["main"]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--replicas", type=int, default=0, help=">0: DFPA dispatch demo")
    ap.add_argument("--chunks", type=int, default=64)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.is_encdec:
        raise SystemExit("serve CLI demonstrates decoder-only archs; see tests for enc-dec")
    params = init_tree(jax.random.PRNGKey(0), model_spec_for(cfg))
    budget = args.prompt_len + args.new_tokens
    eng = ServeEngine(cfg, params, batch=args.batch, seq_budget=budget)

    toks = jax.random.randint(jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size)
    t0 = time.perf_counter()
    out = eng.generate(toks, args.new_tokens)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print(f"generated {out.shape} in {dt:.2f}s ({args.batch * args.new_tokens / dt:.1f} tok/s)")
    print("sample:", np.asarray(out[0][:12]))

    if args.replicas > 0:
        # Heterogeneous replicas: per-chunk decode cost differs per replica
        # and bends with load (the FPM speed function of serving).
        rng = np.random.default_rng(0)
        base = rng.uniform(2e-4, 8e-4, args.replicas)
        caps = rng.integers(args.chunks // 2, args.chunks, args.replicas)

        def replica_run(i, x):
            t = x * base[i]
            if x > caps[i]:  # HBM spill: per-chunk cost grows past capacity
                t += (x - caps[i]) * base[i] * 4.0
            return t

        disp = ReplicaDispatcher(replica_run, args.replicas, eps=0.1)
        res = disp.balance(args.chunks)  # Partition, via the Scheduler facade
        print(
            f"DFPA dispatch over {args.replicas} replicas: d={res.allocations} "
            f"iters={res.iterations} imb={res.imbalance:.3f} converged={res.converged}"
        )


if __name__ == "__main__":
    main()
