"""The partition program's segment table against the per-trip formulation.

``alloc_at_time`` reads a lane-dense ``[..., k-1, p]`` table built once per
program (``modelbank_jax._segments`` / ``_capped``).  These tests hold it to
the formulation it replaced, kept here as ``_alloc_stored_layout``: the same
expressions evaluated on the bank as stored, ``[..., p, k]``, with every
``t``-free term recomputed at each call.  On the CPU backend both are
IEEE-double programs, so the comparison is bit for bit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.partition_scale import make_fleet_bank
from repro.core import modelbank_jax as mbj
from repro.core.modelbank_jax import JaxModelBank

cpu_bit_exact = pytest.mark.skipif(
    jax.default_backend() != "cpu",
    reason="bit-identical results are a CPU-backend contract",
)


def _alloc_stored_layout(xs, ss, counts, t, caps):
    """``alloc_at_time`` on ``[..., p, k]``, every term recomputed per call."""
    dt = xs.dtype
    zero, one = jnp.asarray(0.0, dt), jnp.asarray(1.0, dt)
    t = jnp.asarray(t, dt)
    tb = t[..., None]
    first_x, first_s, last_x, last_s = mbj._edges(xs, ss, counts)
    best = jnp.minimum(tb * first_s, jnp.minimum(first_x, caps))
    k_max = xs.shape[-1]
    if k_max >= 2:
        x0, x1 = xs[..., :-1], xs[..., 1:]
        s0, s1 = ss[..., :-1], ss[..., 1:]
        seg = jnp.arange(k_max - 1)
        valid = (
            (seg < (counts - 1)[..., None])
            & (x0 < caps[..., None])
            & (x1 > x0)
        )
        x1c = jnp.minimum(x1, caps[..., None])
        denom = jnp.where(x1 > x0, x1 - x0, one)
        m = (s1 - s0) / denom
        tseg = tb[..., None]
        a = one - tseg * m
        b = tseg * (s0 - m * x0)
        ub = b / jnp.where(a != zero, a, one)
        cand = jnp.where(
            a > zero,
            jnp.where(ub >= x0, jnp.minimum(ub, x1c), zero),
            jnp.where(
                a == zero,
                jnp.where(b >= zero, x1c, zero),
                jnp.where(x1c >= ub, x1c, zero),
            ),
        )
        cand = jnp.where(valid, cand, zero)
        best = jnp.maximum(best, cand.max(axis=-1))
    ub_r = tb * last_s
    right = (caps > last_x) & (ub_r >= last_x) & (counts > 0)
    best = jnp.maximum(best, jnp.where(right, jnp.minimum(ub_r, caps), zero))
    best = jnp.where((caps > zero) & (counts > 0), best, zero)
    return jnp.where(tb > zero, best, zero)


def _assert_bits_equal(got, want):
    np.testing.assert_array_equal(got.view(np.int64), want.view(np.int64))


# Slopes and segment lengths are powers of two, so every slope m is exact
# and t in TIMES lands a = 1 - t m on zero (m = 1/2 at t = 2, m = 1/4 at
# t = 4), below it (m = 1 at t = 2) and above it; the times below 1 land
# inside the knots.
SLOPES = np.array([-1.0, -0.5, -0.25, 0.0, 0.25, 0.5, 1.0])
TIMES = [0.0, 0.125, 0.25, 0.5, 0.75, 1.0, 2.0, 4.0, 3.7]


def _dyadic_bank(rng, batch, p, k):
    """``[*batch, p, k]`` bank: empty rows, rows padded by repeating their
    last knot, a repeated knot, and caps at, below and between the knots."""
    shape = (*batch, p)
    counts = rng.integers(0, k + 1, shape)
    counts.reshape(-1)[:2] = (0, k)  # an empty row and a full one
    lens = 2.0 ** rng.integers(0, 4, (*shape, k))
    lens[..., 0] = 2.0 ** rng.integers(0, 6, shape)  # the first knot's x
    xs = np.cumsum(lens, axis=-1)
    slopes = rng.choice(SLOPES, (*shape, k))
    slopes[..., 0] = 128.0 + rng.integers(0, 64, shape)  # the first knot's s
    steps = np.concatenate([np.ones((*shape, 1)), lens[..., 1:]], -1)
    ss = np.cumsum(slopes * steps, -1)
    if k >= 3:
        xs[..., 2] = xs[..., 1]  # a zero-length segment
        ss[..., 2] = ss[..., 1]
    last = np.maximum(counts - 1, 0)[..., None]
    pad = np.arange(k) > last
    xs = np.where(pad, np.take_along_axis(xs, last, -1), xs)
    ss = np.where(pad, np.take_along_axis(ss, last, -1), ss)
    xs[counts == 0], ss[counts == 0] = 0.0, 0.0
    knot = np.take_along_axis(xs, rng.integers(0, k, (*shape, 1)), -1)[..., 0]
    caps = np.select(
        [rng.random(shape) < f for f in (0.15, 0.3, 0.45, 0.6)],
        [np.zeros(shape), knot, xs[..., 0] / 2, knot + 0.5],
        np.full(shape, 1e6),
    )
    return xs, ss, counts, caps


BANKS = [pytest.param(b, k, id=f"{'x'.join(map(str, b)) or 'flat'}-k{k}")
         for b in ((), (3,)) for k in (1, 2, 8, 16)]


@cpu_bit_exact
@pytest.mark.parametrize("batch,k", BANKS)
def test_table_matches_stored_layout_bit_for_bit(batch, k):
    rng = np.random.default_rng(100 * k + len(batch))
    xs, ss, counts, caps = _dyadic_bank(rng, batch, 97, k)
    with jax.enable_x64():
        args = tuple(map(jnp.asarray, (xs, ss, counts)))
        caps_d = jnp.asarray(caps)
        bank = JaxModelBank(xs=args[0], ss=args[1], counts=args[2])
        table = jax.jit(mbj._alloc_at_time)
        stored = jax.jit(_alloc_stored_layout)
        for t in TIMES:
            tq = jnp.full(batch, t)
            got = np.asarray(table(*args, tq, caps_d))
            want = np.asarray(stored(*args, tq, caps_d))
            _assert_bits_equal(got, want)
            total = np.asarray(bank.total_alloc(tq, caps_d))
            want_total = np.asarray(jnp.asarray(want).sum(-1))
            _assert_bits_equal(total, want_total)
            if t == 0.0:
                assert not got.any()
    if k >= 2:  # the cases reach every branch of the segment select
        live = np.arange(k - 1) < (counts - 1)[..., None]
        dx = np.diff(xs, axis=-1)
        m = np.diff(ss, axis=-1) / np.where(dx > 0, dx, 1.0)
        a = np.stack([1.0 - t * m[live] for t in TIMES])
        assert (a < 0).any() and (a == 0).any() and (a > 0).any()
        x0 = xs[..., :-1][live]
        assert (np.broadcast_to(caps[..., None], live.shape)[live] <= x0).any()


def _program(xs, ss, counts, caps, n, min_units, fast):
    """One fresh trace of the partition program: a new function object, so
    no trace is shared with another formulation of it."""
    def run(*a):
        return mbj._partition_units_impl(
            *a, 200, jnp.asarray(fast), completion_fast=bool(np.any(fast))
        )

    rel_tol = jnp.asarray(1e-12, xs.dtype)
    return jax.jit(run)(xs, ss, counts, caps, n, min_units, rel_tol)


def _stored_layout_program(monkeypatch, *args):
    """The same program with every trip evaluated by ``_alloc_stored_layout``:
    the "table" is the stored bank itself."""
    with monkeypatch.context() as mp:
        mp.setattr(mbj, "_segments", lambda xs, ss, counts: (xs, ss, counts))
        mp.setattr(mbj, "_capped", lambda segs, caps: (*segs, caps))
        mp.setattr(
            mbj, "_alloc_from_table",
            lambda tab, t: _alloc_stored_layout(*tab[:3], t, tab[3]),
        )
        return _program(*args)


def _fleet(p, seed, k):
    bank = JaxModelBank.from_bank(make_fleet_bank(p, seed=seed))
    xs, ss = bank._padded_to(k)
    return xs, ss, bank.counts


@cpu_bit_exact
@pytest.mark.parametrize("batch", [(), (4,)], ids=["flat", "stacked"])
@pytest.mark.parametrize("fast", [True, False], ids=["threshold", "greedy"])
def test_partition_program_matches_stored_layout(monkeypatch, batch, fast):
    """Same allocations, ``t*`` and trips in every loop, on seeded fleets."""
    p, q = 120, int(np.prod(batch))
    with jax.enable_x64():
        cols = [_fleet(p, 11 + j, 8) for j in range(q)]
        xs, ss, counts = (jnp.stack(a) if batch else a[0] for a in zip(*cols))
        n = jnp.asarray((50 * p + 37 * np.arange(q)).reshape(batch))
        caps = jnp.broadcast_to(n[..., None], counts.shape)
        min_units = jnp.ones_like(counts)
        args = (xs, ss, counts, caps, n, min_units, np.full(batch, fast))
        d, ok, t_star, trips = _program(*args)
        d0, ok0, t0, trips0 = _stored_layout_program(monkeypatch, *args)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))
    _assert_bits_equal(np.asarray(t_star), np.asarray(t0))
    np.testing.assert_array_equal(np.asarray(trips), np.asarray(trips0))
    assert bool(np.all(ok)) and bool(np.all(ok0))
    assert (np.asarray(d).sum(-1) == np.asarray(n)).all()
    assert np.asarray(trips)[1] > 0  # the continuous bisection ran
    assert (np.asarray(trips)[4] > 0) == fast  # the threshold bisection ran
