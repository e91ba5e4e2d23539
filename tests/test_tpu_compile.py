"""Compile the main path's device programs for a TPU v5e, at full size.

The TPU compiler is installed even where no chip is attached: it compiles
for a chip that is only described (``topologies.get_topology_desc``).  These
tests run nothing; they catch what only the chip's compiler refuses (tiling,
fast-memory limits, unsupported float64 ops) before any chip time is spent.
The topology is described inside a fixture, so collecting this file touches
no TPU library, and a host that cannot describe it skips these tests.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core import modelbank_jax as mbj
from repro.kernels.matmul_update import matmul_update_pallas

K_KNOTS = 8  # padded knot width of a bank with up to 8 observed points


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_matmul_update_compiles_to_a_tpu_kernel(one_chip, no_persistent_cache):
    m = k = n = 4096
    c = _spec((m, n), jnp.bfloat16, one_chip)
    a = _spec((m, k), jnp.bfloat16, one_chip)
    b = _spec((k, n), jnp.bfloat16, one_chip)
    compiled = matmul_update_pallas.lower(c, a, b).compile()
    assert "tpu_custom_call" in compiled.as_text()


BANKS = [pytest.param((), 10_000, id="flat-p1e4"),
         pytest.param((16,), 1_000, id="stacked-16x1e3")]


@pytest.mark.parametrize("batch,p", BANKS)
def test_partition_units_compiles(one_chip, no_persistent_cache, batch, p):
    """The threshold completion's program, which holds the greedy loop too
    (for the boundary-tied remainder)."""
    with jax.enable_x64():
        f64, i64 = jnp.float64, jnp.int64
        rows = (*batch, p)
        args = (
            _spec((*rows, K_KNOTS), f64, one_chip),  # xs
            _spec((*rows, K_KNOTS), f64, one_chip),  # ss
            _spec(rows, i64, one_chip),  # counts
            _spec(rows, i64, one_chip),  # caps
            _spec(batch, i64, one_chip),  # n
            _spec(rows, i64, one_chip),  # min_units
            _spec((), f64, one_chip),  # rel_tol
        )
        compiled = mbj._partition_units_jit.lower(
            *args, 200, _spec(batch, jnp.bool_, one_chip),
            completion_fast=True,
        ).compile()
        d, ok, t_star, trips = compiled.out_info
    assert d.shape == rows and d.dtype == np.int64
    assert ok.shape == batch and t_star.shape == batch
    assert trips.shape == (len(mbj.TRIP_LOOPS),) and trips.dtype == np.int32


@pytest.mark.parametrize("batch,p", BANKS)
def test_fold_in_compiles(one_chip, no_persistent_cache, batch, p):
    with jax.enable_x64():
        rows = (*batch, p)
        args = (
            _spec((*rows, K_KNOTS), jnp.float64, one_chip),  # xs
            _spec((*rows, K_KNOTS), jnp.float64, one_chip),  # ss
            _spec(rows, jnp.int64, one_chip),  # counts
            _spec(rows, jnp.float64, one_chip),  # x
            _spec(rows, jnp.float64, one_chip),  # s
            _spec(rows, jnp.bool_, one_chip),  # valid
        )
        compiled = mbj._fold_in_jit.lower(*args).compile()
        xs, ss, counts = compiled.out_info
    assert xs.shape == ss.shape == (*rows, K_KNOTS)
    assert counts.shape == rows
