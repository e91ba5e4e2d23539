"""The comparison that decides ``correct``: a sound run passes; the float32
control and each fault a cell can have fail it.

Every case drives the rest of a run (``run_cell``: set-up, window, release,
check) on the CPU at a tiny size, with the timed path broken underneath.
The fault a cell cannot have: the exchange between chips (every cell runs
on one chip).
"""

import pytest

from bench.run import run_cell

TINY = {"config": {"machines": [[60, "B", 0.5, 0.5], [40, "B", 0.5, 0.25], [20, "C", 1.0, 1.0]],
                   "processors": 120, "units": 960000}}
CELLS = ["flat-1e4-episodes", "flat-1e4-steady"]


def run(workload, **kw):
    result, _, _ = run_cell(workload, 2**31 + 77, 1.0, False, overrides=TINY,
                            t_start=0.0, **kw)
    assert result["attempted"] > 0
    return result


def checks(result):
    return {k: v["value"] for k, v in result["checks"].items()}


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(workload):
    result = run(workload)
    assert result["correct"], result["checks"]
    assert checks(result)["alloc_units"] == 0
    assert result["failed"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_the_float32_control_is_not_correct(workload):
    result = run(workload, control=True)
    assert not result["correct"]
    assert checks(result)["estimate_rel"] > result["checks"]["estimate_rel"]["limit"]


def _plant(monkeypatch, kind):
    from repro.core import Scheduler, SpeedStore

    if kind in ("state_unchanged", "half_batch"):
        fold = SpeedStore.fold_in

        def broken(self, x, s, valid=None):
            # the first fold of a store lands whole, so that it can partition
            if not getattr(self, "_folded_once", False):
                self._folded_once = True
                return fold(self, x, s, valid)
            if kind == "state_unchanged":
                return self
            keep = [i < self.p // 2 for i in range(self.p)]  # half the batch
            valid = keep if valid is None else [bool(v) and k for v, k in zip(valid, keep)]
            return fold(self, x, s, valid)

        monkeypatch.setattr(SpeedStore, "fold_in", broken)
        return
    # one unit moved where an answer is produced: by the partition (autotune)
    # and by a served round (observe)
    partition, observe = SpeedStore.partition, Scheduler.observe

    def moved(d):
        d = list(d)
        d[0], d[1] = d[0] - 1, d[1] + 1
        return d

    def altered_partition(self, *args, **kw):
        d, t = partition(self, *args, **kw)
        return moved(d), t

    def altered_observe(self, times):
        changed = observe(self, times)
        self.d = moved(self.d)
        return changed

    monkeypatch.setattr(SpeedStore, "partition", altered_partition)
    monkeypatch.setattr(Scheduler, "observe", altered_observe)


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_a_planted_fault_is_not_correct(monkeypatch, workload, kind):
    _plant(monkeypatch, kind)
    result = run(workload)
    assert not result["correct"], (kind, result["checks"])
