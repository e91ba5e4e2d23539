"""The comparison that decides ``correct`` in the two-level cell: a sound
run passes; the float32 control and each fault the two-level route can have
fail it.

Every case drives a whole run (``run_cell``: set-up, window, release,
check) on the CPU at a tiny size: 16 nodes of the configuration's three
kinds, 52 processors.
"""

import numpy as np
import pytest

from bench.run import run_cell

CELL = "hier-perlmutter-episodes"
TINY = {"config": {"nodes": [[6, "gpu-a100-40gb", 1, 64, 256, 4, 40],
                             [2, "gpu-a100-80gb", 1, 64, 256, 4, 80],
                             [6, "cpu", 2, 64, 512, 0, 0]],
                   "processors": 52, "units": 152588}}


def run(**kw):
    result, _, _ = run_cell(CELL, 2**33 + 91, 1.0, False, overrides=TINY, t_start=0.0, **kw)
    assert result["attempted"] > 0
    return result


def checks(result):
    return {k: v["value"] for k, v in result["checks"].items()}


def test_a_sound_run_is_correct():
    result = run()
    assert result["correct"], result["checks"]
    assert checks(result)["alloc_units"] == 0
    assert result["failed"] == 0


def test_the_float32_control_is_not_correct():
    result = run(control=True)
    assert not result["correct"]
    assert checks(result)["estimate_rel"] > result["checks"]["estimate_rel"]["limit"]


def _plant(monkeypatch, kind):
    from repro.core import Scheduler, SpeedStore
    from repro.core.hierarchy import Hierarchy

    if kind == "unit_between_groups":
        partition = Hierarchy.partition_units

        def moved(self, *args, **kw):
            d, t = partition(self, *args, **kw)
            first, last = self.index.members[0][0], self.index.members[-1][-1]
            d[first], d[last] = d[first] - 1, d[last] + 1
            return d, t

        monkeypatch.setattr(Hierarchy, "partition_units", moved)
    elif kind == "stale_inner_bank":
        inner, kept = Hierarchy._inner_jax, {}

        def stale(self, *args, **kw):
            # the inner solve reads the bank of the repartition before this one
            prev, kept["bank"] = kept.get("bank"), self.device_bank.copy()
            if prev is not None:
                self.device_bank, self._blocks = prev, None
            return inner(self, *args, **kw)

        monkeypatch.setattr(Hierarchy, "_inner_jax", stale)
    else:  # a fold that skips one group
        init, fold, current = Scheduler.__init__, SpeedStore.fold_in, {}

        def record(self, *args, **kw):
            init(self, *args, **kw)
            current["groups"] = np.asarray(kw.get("groups"))

        def skipping(self, x, s, valid=None):
            # the first fold of a store lands whole, so that it can partition
            if not getattr(self, "_folded_once", False):
                self._folded_once = True
                return fold(self, x, s, valid)
            groups = current["groups"]
            keep = groups != groups[0]
            valid = keep if valid is None else np.asarray(valid, bool) & keep
            return fold(self, x, s, valid.tolist())

        monkeypatch.setattr(Scheduler, "__init__", record)
        monkeypatch.setattr(SpeedStore, "fold_in", skipping)


@pytest.mark.parametrize("kind", ["unit_between_groups", "stale_inner_bank", "fold_skips_group"])
def test_a_planted_fault_is_not_correct(monkeypatch, kind):
    _plant(monkeypatch, kind)
    result = run()
    assert not result["correct"], (kind, result["checks"])
