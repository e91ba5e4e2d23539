"""The per-layer metrics that read the program's own spans: each on a
synthetic window of nested spans, on a window without them (a program that
records none), and on a traced run of each cell on the CPU at a tiny size."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import load
from bench.run import run_cell

ROOT = Path(__file__).resolve().parents[2]
NEW = ["flat.ema_ms", "flat.fold_host_ms", "flat.fold_dispatch_ms",
       "flat.partition_wait_ms", "flat.partition_host_ms", "flat.tune_host_ms"]


def window(spans):
    return SimpleNamespace(t0=0.0, t1=100.0, rounds=[], sessions=[], spans=spans,
                           device=None, compiles=0, rounds_traced=0)


# Two served rounds and one autotune session of two measured rounds, in
# seconds: spans nest as the program records them.
SPANS = {
    "scheduler.observe": [(1.0, 1.010), (2.0, 2.030)],
    "scheduler.ema": [(1.000, 1.002), (2.000, 2.004)],
    "speedstore.fold_in": [(1.002, 1.008), (2.004, 2.010), (10.001, 10.004), (10.011, 10.014)],
    "speedstore.fold_host": [(1.002, 1.004), (2.004, 2.006), (10.001, 10.002), (10.011, 10.012)],
    "modelbank.fold_in": [(1.004, 1.007), (2.006, 2.009), (10.002, 10.004), (10.012, 10.014)],
    "speedstore.partition": [(2.010, 2.030), (10.004, 10.009)],
    "modelbank.partition.wait": [(2.012, 2.026), (10.005, 10.008)],
    "scheduler.autotune": [(9.999, 10.020)],
    "scheduler.measure": [(10.000, 10.001), (10.010, 10.011)],
}


@pytest.mark.parametrize("name,want", [
    ("flat.ema_ms", 3.0),  # (2 + 4) / 2 rounds
    ("flat.fold_host_ms", 1.5),  # (2 + 2 + 1 + 1) / 4 folds
    ("flat.fold_dispatch_ms", 2.5),  # (3 + 3 + 2 + 2) / 4 folds
    ("flat.partition_wait_ms", 8.5),  # (14 + 3) / 2 partitions
    ("flat.partition_host_ms", 4.0),  # (20 - 14 + 5 - 3) / 2 partitions
    # 21 ms of autotune less 2 ms measured, 6 ms folded, 5 ms partitioned,
    # over 2 measured rounds
    ("flat.tune_host_ms", 4.0),
])
def test_reads_nested_spans(name, want):
    assert load("metrics", name).read(window(SPANS)) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reads_nothing_without_the_programs_spans(name):
    # what a program without these spans gives: the facade's only span
    assert load("metrics", name).read(window({"speedstore.partition": [(1.0, 1.1)]})) is None


def test_benchmark_lists_each_new_metric_once():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [m["name"] for m in spec["per_layer"]]
    assert names[-len(NEW):] == NEW
    for m in spec["per_layer"][-len(NEW):]:
        assert m["source"] == "program_span" and m["moves"] == "round_ms"


TINY = {"config": {"machines": [[60, "B", 0.5, 0.5], [40, "B", 0.5, 0.25], [20, "C", 1.0, 1.0]],
                   "processors": 120, "units": 960000},
        "mix": {"trace_after_s": 0.0, "trace_seconds": 0.5}}


@pytest.mark.parametrize("workload", ["flat-1e4-episodes", "flat-1e4-steady"])
def test_a_traced_run_reports_the_span_metrics(workload):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = [m["name"] for m in spec["per_layer"]
              if m["name"] in NEW and workload in m["workloads"]]
    result, _, _ = run_cell(workload, 2**31 + 91, 1.5, True, overrides=TINY, t_start=0.0)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert result["correct"]
    for name in listed:
        assert isinstance(got.get(name), float), (name, got)
    if "flat.partition_wait_ms" in listed:
        parts = got["flat.partition_wait_ms"] + got["flat.partition_host_ms"]
        assert parts == pytest.approx(got["flat.partition_ms"], rel=0.02)
    if "flat.ema_ms" in listed:
        inner = got["flat.ema_ms"] + got["flat.fold_host_ms"] + got["flat.fold_dispatch_ms"]
        assert inner <= got["flat.facade_ms"]
