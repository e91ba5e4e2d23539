"""Benchmark driver: one artifact per paper table/figure + kernel bench +
the roofline table (if dry-run results exist).

    PYTHONPATH=src python -m benchmarks.run [--only tableX] [--out DIR]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from repro.compile_cache import use_compile_cache

from . import paper_tables
from .kernels_bench import kernels_bench
from .roofline import roofline_table

ARTIFACTS = [
    ("table2_dfpa_cost", paper_tables.table2_dfpa_cost),
    ("table3_epsilon", paper_tables.table3_epsilon),
    ("table4_scale", paper_tables.table4_scale),
    ("table5_2d", paper_tables.table5_2d),
    ("fig6_convergence", paper_tables.fig6_convergence),
    ("fig10_compare", paper_tables.fig10_compare),
    ("kernels_bench", kernels_bench),
    ("roofline", roofline_table),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="experiments/bench")
    args = ap.parse_args(argv)
    use_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    rc = 0
    for name, fn in ARTIFACTS:
        if args.only and args.only != name:
            continue
        t0 = time.time()
        try:
            csv = fn()
            path = os.path.join(args.out, f"{name}.csv")
            with open(path, "w") as f:
                f.write(csv)
            print(f"== {name} ({time.time() - t0:.1f}s) -> {path}")
            print(csv)
        except Exception as e:  # noqa: BLE001
            print(f"== {name} FAILED: {type(e).__name__}: {e}")
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
