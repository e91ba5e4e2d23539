"""The paper's computational kernel, TPU-native: blocked ``C += A @ B``.

Hardware adaptation (DESIGN.md §2): the 2011 kernel is a GotoBLAS-style
cache-blocked panel update tuned for L2; the TPU equivalent tiles for VMEM
and the 128x128 MXU:

  * grid (M/bm, N/bn, K/bk), K innermost — the fp32 accumulator scratch
    lives in VMEM across the K sweep (no HBM round-trips for partials);
  * blocks default to 256x256x512 — MXU-aligned (multiples of 128), working
    set (bm*bk + bk*bn + 2*bm*bn fp32) ~ 0.9 MB << 16 MB VMEM, wide enough
    to amortize HBM latency;
  * ``C`` is aliased input->output (a true += update, like the paper's).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["matmul_update_pallas"]


def _kernel(c_in_ref, a_ref, b_ref, c_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = c_in_ref[...].astype(jnp.float32)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _flush():
        c_ref[...] = acc_ref[...].astype(c_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def matmul_update_pallas(
    c: jax.Array,  # (M, N)
    a: jax.Array,  # (M, K)
    b: jax.Array,  # (K, N)
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    M, K = a.shape
    K2, N = b.shape
    assert K == K2 and c.shape == (M, N)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"shape ({M},{N},{K}) not divisible by blocks ({bm},{bn},{bk})")
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),  # C in
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),  # A
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),  # B
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), c.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(c, a, b)
