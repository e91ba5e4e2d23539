"""Measure the series of round times that the ``steady`` mix replays.

    python3 bench/measure_noise.py --block-ms 6 --samples 256 \
        --out bench/traffic/steady-round-times.csv

Times ``--samples`` back-to-back blocks of single-threaded float64 matrix
multiplication on the host's CPU by the host's clock, each block sized to
take about ``--block-ms`` (a converged round of ``cluster-1e4`` lasts about
6 ms), and writes one time in seconds per line, the host and the block
first as comments.  The benchmark's runs only read the file.
"""

from __future__ import annotations

import os

for var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ[var] = "1"

import argparse  # noqa: E402
import platform  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402


def block(a: np.ndarray, reps: int) -> float:
    t0 = time.perf_counter()
    for _ in range(reps):
        a @ a
    return time.perf_counter() - t0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--block-ms", type=float, default=6.0)
    ap.add_argument("--samples", type=int, default=256)
    ap.add_argument("--size", type=int, default=128)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    a = np.random.default_rng(0).standard_normal((args.size, args.size))
    for _ in range(50):  # warm the caches and the clock
        a @ a
    one = np.median([block(a, 20) / 20 for _ in range(20)])
    reps = max(1, round(args.block_ms * 1e-3 / one))
    times = [block(a, reps) for _ in range(args.samples)]
    with open(args.out, "w") as f:
        f.write(f"# {args.samples} blocks of {reps} float64 {args.size}x{args.size} matmuls, "
                f"one thread, host clock; {platform.machine()} {platform.processor() or ''}, "
                f"{os.cpu_count()} cores\n")
        for t in times:
            f.write(f"{t!r}\n")
    med = float(np.median(times))
    print(f"reps {reps} median_ms {1e3 * med:.4f} min/median {min(times) / med:.4f} "
          f"max/median {max(times) / med:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
