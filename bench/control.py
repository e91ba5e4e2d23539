"""Readings that the limits of ``bench/limits/<workload>.json`` are set from.

    python3 bench/control.py --workload <name> --seeds 12 --control-seeds 3 \
        --seconds <s> [--first-seed <n>]

Runs the cell in this one process (one process holds the chip): first the
program as configured on ``--seeds`` seeds, then the float32 control (the
program's own float32 path, one precision below the configuration's
float64) on ``--control-seeds`` seeds, and prints one JSON line per run with
every compared number.  The limits take the largest sound reading as the
lower end and the smallest control reading as the upper end.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench.run import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first-seed", type=int, default=2_000_000_000)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    runs = [(False, args.first_seed + k) for k in range(args.seeds)]
    runs += [(True, args.first_seed + 1000 + k) for k in range(args.control_seeds)]
    for control, seed in runs:
        try:
            result, _, info = run_cell(args.workload, seed, args.seconds, False, control=control,
                                       t_start=time.perf_counter())
        except Exception as e:  # a control that crashes has failed; record it
            print(json.dumps({"seed": seed, "control": control, "error": repr(e)}), flush=True)
            continue
        print(json.dumps({
            "seed": seed, "control": control, "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "checks": {k: v["value"] for k, v in result["checks"].items()},
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "check_s": info[-1]["check_s"], "window": info[1],
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
