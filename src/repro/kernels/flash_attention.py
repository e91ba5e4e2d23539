"""Flash attention (online softmax) Pallas TPU kernel.

Supports the features the assigned archs need: causal, sliding-window
(gemma2/recurrentgemma local layers), attention-logit softcap (gemma2),
GQA (KV-head index map = q_head // group), right-aligned queries (prefill
continuation).  fp32 running max / sum / accumulator in VMEM scratch; KV
innermost grid dim sweeps sequentially so the scratch carries across blocks.

Queries are right-aligned to the keys: query row i sits at absolute position
``i + (Sk - Sq)`` — the standard decode/prefill convention.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

NEG_INF = -2.0e38


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, window: int, softcap: float,
    bq: int, bk: int, nk: int, q_off: int,
):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qb = pl.program_id(1)
    q_start = qb * bq + q_off  # absolute position of first query row
    k_start = kb * bk

    # Block-level skip: entire KV block out of visible range?
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + bq - 1  # some key <= some query pos
    if window > 0:
        run &= k_start + bk - 1 >= q_start - window + 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=-1)[:, None]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1)[:, None]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0, 0], preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(kb == nk - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "bq", "bk", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, Kv, Sk, D)
    v: jax.Array,  # (B, Kv, Sk, D)
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, D = q.shape
    Kv, Sk = k.shape[1], k.shape[2]
    G = H // Kv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    if Sq % bq or Sk % bk:
        raise ValueError(f"seq ({Sq},{Sk}) not divisible by blocks ({bq},{bk})")
    nk = Sk // bk
    grid = (B * H, Sq // bq, nk)
    q_off = Sk - Sq

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, nk=nk, q_off=q_off,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda bh, qb, kb: (bh // H, bh % H, qb, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda bh, qb, kb: (bh // H, (bh % H) // G, kb, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda bh, qb, kb: (bh // H, (bh % H) // G, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda bh, qb, kb: (bh // H, bh % H, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),  # running max
            pltpu.VMEM((bq, 1), jnp.float32),  # running sum
            pltpu.VMEM((bq, D), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return out
