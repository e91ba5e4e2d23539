"""Chunked RG-LRU linear-recurrence Pallas TPU kernel.

Computes ``h_t = exp(log_a_t) * h_{t-1} + b_t`` along the sequence.  The grid
is (batch, D/bd, S/bs) with the sequence dim innermost-sequential: a VMEM
scratch carries the running state across sequence blocks, and the intra-block
recurrence uses a log-depth associative scan — O(S/bs) HBM sweeps with no
host-level sequential launch, the TPU-native replacement for the per-element
CPU recurrence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rglru_scan_pallas"]


def _kernel(la_ref, b_ref, h_ref, carry_ref, *, ns: int):
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    la = la_ref[0]  # (bs, bd) fp32
    b = b_ref[0]

    def combine(left, right):
        la_l, b_l = left
        la_r, b_r = right
        return la_l + la_r, jnp.exp(la_r) * b_l + b_r

    la_c, b_c = jax.lax.associative_scan(combine, (la, b), axis=0)
    h = b_c + jnp.exp(la_c) * carry_ref[...]
    h_ref[0] = h.astype(h_ref.dtype)
    carry_ref[...] = h[-1:]


@functools.partial(jax.jit, static_argnames=("bs", "bd", "interpret"))
def rglru_scan_pallas(
    log_a: jax.Array,  # (B, S, D) fp32
    b: jax.Array,  # (B, S, D) fp32
    *,
    bs: int = 256,
    bd: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, S, D = log_a.shape
    bs = min(bs, S)
    bd = min(bd, D)
    if S % bs or D % bd:
        raise ValueError(f"(S={S}, D={D}) not divisible by blocks ({bs},{bd})")
    ns = S // bs
    grid = (B, D // bd, ns)
    return pl.pallas_call(
        functools.partial(_kernel, ns=ns),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, bd), lambda bb, db, sb: (bb, sb, db)),
            pl.BlockSpec((1, bs, bd), lambda bb, db, sb: (bb, sb, db)),
        ],
        out_specs=pl.BlockSpec((1, bs, bd), lambda bb, db, sb: (bb, sb, db)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), log_a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(log_a, b)
