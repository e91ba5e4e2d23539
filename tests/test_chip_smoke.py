"""``chip_smoke.py``'s phases at a tiny size on the CPU.

The script itself refuses to run anywhere but a TPU; these tests call its
phase bodies directly, so their comparisons and the entry points they drive
stay in working order between chip runs.  On the CPU, under x64, the jax and
numpy banks agree bit for bit, so every unit difference is 0 here.
"""

import pytest

import jax

import chip_smoke


def test_flat_phase_tiny():
    with jax.enable_x64():
        line = chip_smoke.phase_flat(p=200, n=20_000, serve_rounds=3)
    assert line["first_partition_max_unit_diff"] == 0
    assert line["serve_max_unit_diff_vs_numpy"] == 0
    assert line["rounds_jax"] == line["rounds_numpy"]
    assert line["observe_repartitions"] > 0 and line["carry_donated"]


def test_fleet_phase_tiny():
    with jax.enable_x64():
        out = chip_smoke.phase_fleet(q=3, p=50)
    for mode in ("sync", "pipeline"):
        line = out[mode]
        assert line["round0_max_unit_diff"] == 0
        assert line["rebalance_max_unit_diff"] == 0
        assert line["max_iterations"] > 1  # the tenants had to measure
        assert line["rebalance_new_compiles"] == {"partition": 0, "fold": 0}


def test_kernel_phase_tiny():
    line = chip_smoke.phase_kernel(256, 512, 256, impl="interpret")
    assert line["max_abs_err"] <= line["atol"]


def test_hierarchy_phase_tiny():
    ndev = len(jax.devices())
    with jax.enable_x64():
        line = chip_smoke.phase_hierarchy_sharded(8, 40, ndev=ndev)
    assert line["devices"] == ndev
    assert line["max_unit_diff"] == 0
    assert line["max_shard_elems"] * ndev <= line["flat_bank_elems"]


def test_a_failed_comparison_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="sum"):
        chip_smoke._check_alloc([3, 3], 7, 1, [10, 10], "pair")


def test_refuses_to_run_off_the_chip(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
