"""Each mix is a pure function of the seed, periodic in the round index, and
the same work for every seed: the seed only orders fixed tables.  The truth
is the configuration's machine table, and its paging region is reached."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench.harness import load as load_module
from bench.loops.steady import read_factors

BENCH = Path(__file__).resolve().parents[1]
TINY = {"machines": [[60, "B", 0.5, 0.5], [40, "B", 0.5, 0.25], [20, "C", 1.0, 1.0]],
        "processors": 120, "units": 960000}


def load(mix, **over):
    cfg = json.loads((BENCH / "configs" / "cluster-1e4.json").read_text())
    cfg.update(over)
    m = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    return cfg, m


def loop(mix, seed):
    cfg, m = load(mix, **TINY)
    return load_module("loops", m["loop"]).Loop(cfg, m, seed)


def test_the_truth_is_the_machine_table():
    cfg, _ = load("episodes")
    truth = load_module("truth", cfg["truth"])
    cpu, mem = truth.classes(cfg)
    assert cpu.size == cfg["processors"] == sum(r[0] for r in cfg["machines"])
    xs, ss = truth.build(cfg, 0)
    assert xs.shape == (cfg["processors"], 6)
    np.testing.assert_array_equal(xs, truth.build(cfg, 0)[0])
    # the plateau follows the CPU capacity, within the class spread
    plateau = ss[:, 2]
    assert plateau.max() / plateau.min() > 3.0
    # the even split already runs a share of the machines past their knee,
    # where the speed falls
    knee = xs[:, -1] / 8.0
    even = cfg["units"] / cfg["processors"]
    paging = even > knee
    assert 0.2 < paging.mean() < 0.5
    assert np.all(ss[paging, -1] < ss[paging, 2])


@pytest.mark.parametrize("truth_seed", [0, 1, 2])
def test_every_truth_draw_converges_within_max_iter(truth_seed):
    """A cold start on the whole cell reaches eps before ``max_iter``, so
    every session of the episodes cell reports ``tune_s`` (the program's
    numpy backend at full size; the estimates are a [12583, k] bank)."""
    from bench.flat import piecewise_time
    from repro.core import Policy, Scheduler, SpeedStore

    cfg, _ = load("episodes")
    assert truth_seed in cfg["truth_seeds"]
    xs, ss = load_module("truth", cfg["truth"]).build(cfg, truth_seed)

    class Executor:
        num_procs = xs.shape[0]

        def run(self, d):
            return piecewise_time(xs, ss, np.asarray(d)).tolist()

    sched = Scheduler(SpeedStore.empty(xs.shape[0], backend="numpy"), policy=Policy.DFPA,
                      eps=cfg["eps"], min_units=cfg["min_units"], smooth=cfg["smooth"])
    res = sched.autotune(Executor(), cfg["units"], cfg["eps"], max_iter=cfg["max_iter"],
                         min_units=cfg["min_units"])
    assert res.converged
    assert res.iterations <= cfg["max_iter"] // 2  # room for the float64 device path


@pytest.mark.parametrize("mix", ["episodes", "steady"])
def test_a_mix_is_a_function_of_the_seed(mix):
    a, b = loop(mix, 2**31 + 5), loop(mix, 2**31 + 5)
    for (xa, sa), (xb, sb) in zip(a.clusters, b.clusters):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(sa, sb)
    if mix == "episodes":
        np.testing.assert_array_equal(a.order, b.order)
    else:
        np.testing.assert_array_equal(a.factors, b.factors)


@pytest.mark.parametrize("mix", ["episodes", "steady"])
def test_another_seed_orders_the_same_tables(mix):
    loops = [loop(mix, 2**40 + k) for k in range(8)]
    a = loops[0]
    for c in loops[1:]:
        for (xa, sa), (xc, sc) in zip(a.clusters, c.clusters):
            np.testing.assert_array_equal(xa, xc)
            np.testing.assert_array_equal(sa, sc)
    table = (lambda lp: lp.order) if mix == "episodes" else (lambda lp: lp.factors)
    for c in loops[1:]:
        np.testing.assert_array_equal(np.sort(table(c)), np.sort(table(a)))
    assert any(not np.array_equal(table(c), table(a)) for c in loops[1:])


def test_steady_factors_repeat_with_their_period():
    lp = loop("steady", 11)
    period = len(lp.factors)
    assert period == len(read_factors(lp.mix["factors"]))
    for r in range(2 * period):
        assert lp.factor(r) == lp.factor(r + period)
    assert len({lp.factor(r) for r in range(period)}) > period // 2
    assert abs(np.median(lp.factors) - 1.0) < 1e-12
