"""Fuzz-parity harness: scalar vs numpy-bank vs jax-bank, locked together.

Three implementations of the partitioning algorithm coexist (see the "three
backends, one semantics" section in ``core/modelbank.py``); this suite is
what makes that safe.  Every property runs twice:

  * a **hypothesis** lane (through the optional ``tests/_hyp.py`` shim;
    skipped cleanly when hypothesis is not installed), >= 200 generated
    cases per property;
  * a **numpy-rng** lane that always runs, 200 seeded cases per property,
    so minimal environments still exercise the parity surface.

Both lanes drive the same ``_check_*`` functions over randomly generated
banks *including the degenerate rows*: empty models, single-point models,
duplicate x's (collapsed by the FPM update rule), and zero caps.

Parity contract asserted here:

  * ``speed`` / ``time`` / ``alloc_at_time`` bit-identical between the numpy
    and jax banks (x64), elementwise equal to the scalar models on non-empty
    rows, NaN on empty rows for both banks;
  * ``partition_units``: all three paths sum to ``n``, respect caps and
    ``min_units``, the numpy and jax banks agree bit-for-bit, and all three
    hit the same makespan (allocations may tie-break differently between the
    scalar and banked continuous solvers; the makespan must not drift);
  * infeasible inputs raise ``ValueError`` on all three paths (including the
    ``cap < min_units`` silent-shortfall case this PR fixed);
  * ``fold_in`` (the device-resident DFPA carry) reproduces the scalar
    ``add_point`` update rule exactly, duplicates included;
  * the stacked ``[q, p, k]`` bank partitions every column exactly as the
    per-column calls do.

The jax lane runs under ``jax.enable_x64`` so its float ops are
IEEE-double identical to numpy's — that is what makes bit-equality a fair
assertion (float32 would differ by a unit here and there).
"""

import numpy as np
import pytest

from _hyp import given, settings, st

import jax

from repro.core import (
    BatchedSimulatedExecutor,
    ModelBank,
    PiecewiseLinearFPM,
    SimulatedExecutor,
    dfpa,
    make_hcl_time_fn_batch,
    make_hcl_time_fns,
    partition_units,
    speed_fn_2d,
    speed_fn_2d_batch,
    time_fn_2d_batch,
)
from repro.core.modelbank_jax import JaxModelBank
from repro.core.partition import _partition_units_bank, _prep_unit_caps
from repro.core.partition2d import bank_repartition_2d
from repro.runtime.balance import BalanceController

K_PAD = 8  # pad every jax bank to one width -> one jit compile per p

# Bit-equality with numpy relies on XLA's sum-reduction order matching
# numpy's — contractually true only where both run on the same CPU FPU.  On
# accelerator backends a 1-ulp reduction difference can legitimately move a
# boundary unit, so there the parity contract relaxes to identical makespans.
BIT_EXACT = jax.default_backend() == "cpu"
cpu_bit_exact = pytest.mark.skipif(
    not BIT_EXACT, reason="bit-identical traces are a CPU-backend contract"
)


def _jax_bank(bank: ModelBank) -> JaxModelBank:
    jb = JaxModelBank.from_bank(bank)
    xs, ss = jb._padded_to(K_PAD)
    return JaxModelBank(xs=xs, ss=ss, counts=jb.counts)


# ---------------------------------------------------------------------------
# Case generation: one description drives both fuzz lanes
# ---------------------------------------------------------------------------


def _case_from_raw(rows, n, caps_frac, min_units):
    """rows: per-processor point lists (possibly empty / duplicated xs)."""
    models = [PiecewiseLinearFPM.from_points(r) for r in rows]
    return dict(models=models, n=n, caps_frac=caps_frac, min_units=min_units)


def _random_rows(rng, p, allow_empty=True):
    rows = []
    for _ in range(p):
        k = int(rng.integers(0 if allow_empty else 1, 8))
        if k == 0:
            rows.append([])
            continue
        xs = rng.uniform(1.0, 1e4, k)
        if rng.random() < 0.3:  # provoke duplicate x's (FPM replaces)
            xs = np.round(xs / 100.0) * 100.0 + 1.0
        ss = rng.uniform(0.5, 500.0, k)
        rows.append(list(zip(xs.tolist(), ss.tolist())))
    return rows


def _random_case(rng, allow_empty=True):
    p = int(rng.integers(1, 9))
    rows = _random_rows(rng, p, allow_empty=allow_empty)
    n = int(rng.integers(max(2 * p, 4), 3000))
    caps_frac = rng.uniform(0.0, 1.0, p).tolist()
    min_units = int(rng.integers(0, 3))
    return _case_from_raw(rows, n, caps_frac, min_units)


# Strategy construction parses under the no-hypothesis shim too (the shim's
# `st` yields stubs; `given` then skips the test before anything runs).
@st.composite
def _cases(draw, allow_empty=True):
    p = draw(st.integers(min_value=1, max_value=8))
    rows = []
    for _ in range(p):
        k = draw(st.integers(min_value=0 if allow_empty else 1, max_value=7))
        pts = draw(
            st.lists(
                st.tuples(
                    st.floats(min_value=1.0, max_value=1e4,
                              allow_nan=False, allow_infinity=False),
                    st.floats(min_value=0.5, max_value=500.0,
                              allow_nan=False, allow_infinity=False),
                ),
                min_size=k,
                max_size=k,
            )
        )
        rows.append(pts)
    n = draw(st.integers(min_value=max(2 * p, 4), max_value=3000))
    caps_frac = draw(
        st.lists(st.floats(min_value=0.0, max_value=1.0),
                 min_size=p, max_size=p)
    )
    min_units = draw(st.integers(min_value=0, max_value=2))
    return _case_from_raw(rows, n, caps_frac, min_units)


# ---------------------------------------------------------------------------
# Property 1: model queries — scalar vs numpy bank vs jax bank
# ---------------------------------------------------------------------------


def _check_query_parity(case, rng):
    models = case["models"]
    p = len(models)
    bank = ModelBank.from_models(models)
    x = rng.uniform(0.0, 2e4, p)
    t = float(rng.uniform(1e-3, 100.0))
    caps = rng.uniform(0.0, 1e4, p)
    caps[rng.random(p) < 0.15] = 0.0  # zero caps -> zero allocation

    s_np, t_np = bank.speed(x), bank.time(x)
    a_np = bank.alloc_at_time(t, caps)
    with jax.enable_x64():
        jb = _jax_bank(bank)
        s_jx = np.asarray(jb.speed(x))
        t_jx = np.asarray(jb.time(x))
        a_jx = np.asarray(jb.alloc_at_time(t, caps))

    # numpy vs jax: bit-identical on CPU, tight allclose elsewhere; NaN
    # pattern (empty rows) must agree either way
    if BIT_EXACT:
        assert np.array_equal(s_np, s_jx, equal_nan=True)
        assert np.array_equal(t_np, t_jx, equal_nan=True)
        assert np.array_equal(a_np, a_jx)
    else:
        assert np.allclose(s_np, s_jx, rtol=1e-12, equal_nan=True)
        assert np.allclose(t_np, t_jx, rtol=1e-12, equal_nan=True)
        assert np.allclose(a_np, a_jx, rtol=1e-12, atol=1e-12)

    # banks vs scalar models on non-empty rows
    for i, m in enumerate(models):
        if m.num_points == 0:
            assert np.isnan(s_np[i])
            assert a_np[i] == 0.0
            continue
        assert s_np[i] == m.speed(float(x[i]))
        assert t_np[i] == m.time(float(x[i]))
        assert a_np[i] == pytest.approx(m.alloc_at_time(t, float(caps[i])), rel=1e-10, abs=1e-10)


@pytest.mark.slow
def test_query_parity_fuzz_numpy_lane():
    rng = np.random.default_rng(101)
    for _ in range(200):
        _check_query_parity(_random_case(rng), rng)


@pytest.mark.slow
@given(case=_cases())
@settings(max_examples=200, deadline=None)
def test_query_parity_fuzz_hypothesis(case):
    _check_query_parity(case, np.random.default_rng(0))


# ---------------------------------------------------------------------------
# Property 2: partition_units — identical makespans on all three paths
# ---------------------------------------------------------------------------


def _makespan(models, d):
    return max(m.time(float(di)) for m, di in zip(models, d))


def _check_partition_parity(case):
    models = [m for m in case["models"] if m.num_points > 0]
    p = len(models)
    if p == 0:
        return
    n, min_units = case["n"], min(case["min_units"], case["n"] // max(p, 1))
    lo = max(1, min_units)
    caps = [lo + int(f * n) for f in case["caps_frac"][:p]]
    if sum(c if c < n else n for c in caps) < n:
        return  # infeasible caps are property 3's subject
    bank = ModelBank.from_models(models)

    d_scalar = partition_units(models, n, caps, min_units=min_units, vectorize=False)
    d_bank = partition_units(bank, n, caps, min_units=min_units)
    with jax.enable_x64():
        d_jax = partition_units(_jax_bank(bank), n, caps, min_units=min_units, backend="jax")

    for d in (d_scalar, d_bank, d_jax):
        assert sum(d) == n
        assert all(min_units <= di <= ci for di, ci in zip(d, caps))
    # numpy bank vs jax bank: bit-identical allocations (CPU contract; on
    # accelerators the makespan assertion below is the binding one)
    if BIT_EXACT:
        assert d_bank == d_jax
    # all three: identical makespans (tie-breaks may differ, the metric not)
    ms = [_makespan(models, d) for d in (d_scalar, d_bank, d_jax)]
    assert max(ms) - min(ms) <= 1e-9 * max(ms)

    # fourth path: the threshold-count completion on monotone banks (auto
    # routing demotes the rest — tests/test_completion.py proves that),
    # checked against the FORCED per-unit greedy so the comparison stays
    # fast-vs-exact even though "auto" (used by d_bank above) already picks
    # the threshold path here.  Makespans must be bit-identical.
    if bank.is_monotone():
        icaps = list(_prep_unit_caps(p, n, caps, min_units))
        d_thr, _ = _partition_units_bank(
            bank, n, icaps, min_units=min_units, completion="threshold"
        )
        d_greedy, _ = _partition_units_bank(
            bank, n, icaps, min_units=min_units, completion="greedy"
        )
        with jax.enable_x64():
            d_thr_jax = _jax_bank(bank).partition_units(
                n, caps, min_units=min_units, completion="threshold"
            )
        assert sum(d_thr) == n
        assert all(min_units <= di <= ci for di, ci in zip(d_thr, caps))
        assert _makespan(models, d_thr) == _makespan(models, d_greedy)
        if BIT_EXACT:
            assert d_thr == d_greedy == d_bank
            assert list(map(int, d_thr_jax)) == d_thr


@pytest.mark.slow
def test_partition_parity_fuzz_numpy_lane():
    rng = np.random.default_rng(202)
    for _ in range(200):
        _check_partition_parity(_random_case(rng, allow_empty=False))


@pytest.mark.slow
@given(case=_cases(allow_empty=False))
@settings(max_examples=200, deadline=None)
def test_partition_parity_fuzz_hypothesis(case):
    _check_partition_parity(case)


# ---------------------------------------------------------------------------
# Property 3: infeasible inputs raise the same ValueError on all three paths
# ---------------------------------------------------------------------------


def _check_infeasible_parity(case):
    models = [m for m in case["models"] if m.num_points > 0]
    p = len(models)
    if p == 0:
        return
    bank = ModelBank.from_models(models)
    n = case["n"]

    variants = [
        # min_units * p > n (sum of mins exceeds the total)
        dict(n=p * 2 - 1, caps=None, min_units=2),
        # some cap below min_units (the silent-shortfall regression)
        dict(n=n, caps=[0] + [n] * (p - 1), min_units=1),
        # sum(caps) < n
        dict(n=n, caps=[max(n // (2 * p) - 1, 0)] * p, min_units=0),
    ]
    for kw in variants:
        for path_kw, src in (
            (dict(vectorize=False), models),
            (dict(), bank),
            (dict(backend="jax"), bank),
        ):
            with pytest.raises(ValueError):
                with jax.enable_x64():
                    partition_units(src, kw["n"], kw["caps"],
                                    min_units=kw["min_units"], **path_kw)


@pytest.mark.slow
def test_infeasible_parity_fuzz_numpy_lane():
    rng = np.random.default_rng(303)
    for _ in range(200):
        _check_infeasible_parity(_random_case(rng, allow_empty=False))


@pytest.mark.slow
@given(case=_cases(allow_empty=False))
@settings(max_examples=200, deadline=None)
def test_infeasible_parity_fuzz_hypothesis(case):
    _check_infeasible_parity(case)


def test_query_parity_smoke():
    rng = np.random.default_rng(111)
    for _ in range(25):
        _check_query_parity(_random_case(rng), rng)


def test_partition_parity_smoke():
    rng = np.random.default_rng(222)
    for _ in range(25):
        _check_partition_parity(_random_case(rng, allow_empty=False))


def test_infeasible_parity_smoke():
    rng = np.random.default_rng(333)
    for _ in range(10):
        _check_infeasible_parity(_random_case(rng, allow_empty=False))


def test_fold_in_parity_smoke():
    rng = np.random.default_rng(444)
    for _ in range(25):
        _check_fold_in_parity(rng)


def test_min_units_cap_shortfall_raises_on_all_paths():
    """Regression: caps[i] < min_units used to be silently absorbed by
    over-allocating the other processors; now every path refuses."""
    models = [PiecewiseLinearFPM.from_points([(10.0, 5.0), (100.0, 4.0)]) for _ in range(4)]
    bank = ModelBank.from_models(models)
    for src, kw in (
        (models, dict(vectorize=False)),
        (bank, dict()),
        (bank, dict(backend="jax")),
    ):
        with pytest.raises(ValueError, match="min_units"):
            with jax.enable_x64():
                partition_units(src, 20, caps=[1, 20, 20, 20], min_units=2, **kw)


def test_empty_model_with_positive_cap_raises_on_bank_paths():
    models = [PiecewiseLinearFPM(), PiecewiseLinearFPM.from_points([(10.0, 5.0)])]
    bank = ModelBank.from_models(models)
    with pytest.raises(ValueError):
        partition_units(bank, 10)
    with jax.enable_x64():
        with pytest.raises(ValueError):
            partition_units(_jax_bank(bank), 10, backend="jax")


# ---------------------------------------------------------------------------
# Property 4: fold_in == the scalar add_point update rule
# ---------------------------------------------------------------------------


def _check_fold_in_parity(rng):
    p = int(rng.integers(1, 8))
    models = [PiecewiseLinearFPM() for _ in range(p)]
    with jax.enable_x64():
        jb = JaxModelBank.empty(p, k=2)
        for _ in range(int(rng.integers(1, 14))):
            x = np.round(rng.uniform(1, 25, p))  # small ints -> many duplicates
            s = rng.uniform(0.5, 10.0, p)
            valid = rng.random(p) > 0.25
            for i in range(p):
                if valid[i]:
                    models[i].add_point(float(x[i]), float(s[i]))
            jb = jb.fold_in(x, s, valid)
        got = jb.to_bank()
    for i in range(p):
        assert got.row(i).as_points() == models[i].as_points()


@pytest.mark.slow
def test_fold_in_parity_fuzz_numpy_lane():
    rng = np.random.default_rng(404)
    for _ in range(200):
        _check_fold_in_parity(rng)


@pytest.mark.slow
@given(seed=st.integers(min_value=0, max_value=10**6))
@settings(max_examples=200, deadline=None)
def test_fold_in_parity_fuzz_hypothesis(seed):
    _check_fold_in_parity(np.random.default_rng(seed))


# ---------------------------------------------------------------------------
# Stacked [q, p, k] bank: every column's t* bisects simultaneously
# ---------------------------------------------------------------------------


@cpu_bit_exact
def test_stacked_bank_matches_per_column():
    rng = np.random.default_rng(7)
    q, p, n = 5, 6, 400
    col_models = [
        [
            PiecewiseLinearFPM.from_points(
                sorted(zip(rng.uniform(1, 1e4, 5), rng.uniform(0.5, 500.0, 5)))
            )
            for _ in range(p)
        ]
        for _ in range(q)
    ]
    with jax.enable_x64():
        banks = [JaxModelBank.from_models(ms) for ms in col_models]
        stacked = JaxModelBank.stack(banks)
        d_all = stacked.partition_units(n, min_units=1)
        ns = np.array([n + 37 * j for j in range(q)])
        d_var = stacked.partition_units(ns, min_units=1)
    for j in range(q):
        want = partition_units(ModelBank.from_models(col_models[j]), n, min_units=1)
        assert list(d_all[j]) == want
        want_var = partition_units(
            ModelBank.from_models(col_models[j]), int(ns[j]), min_units=1
        )
        assert list(d_var[j]) == want_var


def test_stacked_bank_rejected_by_flat_partition_api():
    """The flat List[int] API can't express [q, p] results; it must say so
    instead of crashing with an opaque conversion TypeError."""
    ms = [PiecewiseLinearFPM.from_points([(10.0, 5.0), (100.0, 4.0)])] * 3
    with jax.enable_x64():
        stacked = JaxModelBank.stack([JaxModelBank.from_models(ms)] * 2)
        with pytest.raises(ValueError, match="stacked"):
            partition_units(stacked, 30, backend="jax")
        with pytest.raises(ValueError, match="unbatched"):
            stacked.to_bank()


def test_bank_repartition_2d_rejects_unknown_backend():
    with pytest.raises(ValueError, match="backend"):
        bank_repartition_2d([[PiecewiseLinearFPM()]], [[None]], [1], 4, backend="Jax")


@cpu_bit_exact
def test_bank_repartition_2d_matches_numpy_backend():
    rng = np.random.default_rng(11)
    p, q, M = 4, 3, 256
    specs, _ = make_hcl_time_fns(2048)
    g_batch = speed_fn_2d_batch(specs[: p * q])
    widths = [90, 80, 86]
    fpms = [[PiecewiseLinearFPM() for _ in range(q)] for _ in range(p)]
    fpm_width = [[None] * q for _ in range(p)]
    for i in range(p):
        for j in range(q):
            w = widths[j]
            for r in rng.uniform(4, M, 5):
                mb = np.full(p * q, float(r))
                nb = np.full(p * q, float(w))
                fpms[i][j].add_point(float(r), float(g_batch(mb, nb)[i * q + j]) / w)
            fpm_width[i][j] = w
    with jax.enable_x64():
        rows_jax = bank_repartition_2d(fpms, fpm_width, widths, M, backend="jax")
    rows_np = bank_repartition_2d(fpms, fpm_width, widths, M, backend="numpy")
    assert rows_jax == rows_np
    assert all(sum(r) == M for r in rows_jax)


def test_speed_fn_2d_batch_matches_scalar():
    specs, _ = make_hcl_time_fns(2048)
    gb = speed_fn_2d_batch(specs)
    tb = time_fn_2d_batch(specs)
    P = len(specs)
    rng = np.random.default_rng(3)
    for _ in range(25):
        mb = rng.uniform(0.0, 4000.0, P)
        mb[rng.random(P) < 0.1] = 0.0
        nb = rng.uniform(1.0, 4000.0, P)
        want = [speed_fn_2d(s)(float(m), float(w)) for s, m, w in zip(specs, mb, nb)]
        np.testing.assert_allclose(gb(mb, nb), want, rtol=1e-12)
        want_t = [
            (m * w) / sv if m * w > 0 else 0.0 for m, w, sv in zip(mb, nb, want)
        ]
        np.testing.assert_allclose(tb(mb, nb), want_t, rtol=1e-12)


# ---------------------------------------------------------------------------
# End-to-end: DFPA and the BalanceController on the jax backend
# ---------------------------------------------------------------------------


@cpu_bit_exact
def test_dfpa_jax_backend_reproduces_numpy_history():
    n = 2048
    _, tb = make_hcl_time_fn_batch(n)
    p = 15

    def mk():
        return BatchedSimulatedExecutor(
            time_fn_batch=lambda r: tb(np.asarray(r, float) * n), p=p
        )

    r_np = dfpa(mk(), n, eps=0.025, min_units=1)
    with jax.enable_x64():
        r_jx = dfpa(mk(), n, eps=0.025, min_units=1, backend="jax")
    assert r_np.d == r_jx.d
    assert r_np.iterations == r_jx.iterations
    assert [h[0] for h in r_np.history] == [h[0] for h in r_jx.history]


@cpu_bit_exact
def test_balance_controller_jax_backend_matches_numpy():
    def run(backend):
        if backend == "jax":
            with jax.enable_x64():
                return _run(backend)
        return _run(backend)

    def _run(backend):
        ctl = BalanceController(n_units=64, num_groups=4, eps=0.05, backend=backend)
        speeds = [4.0, 4.0, 4.0, 2.0]
        trace = []
        for _ in range(6):
            times = [d / s for d, s in zip(ctl.d, speeds)]
            ctl.observe(times)
            trace.append(list(ctl.d))
        return ctl, trace

    ctl_np, trace_np = run("numpy")
    ctl_jx, trace_jx = run("jax")
    assert trace_np == trace_jx
    assert ctl_np.rebalances == ctl_jx.rebalances
    # the device snapshot agrees with the scalar models it mirrors
    with jax.enable_x64():
        snap = ctl_jx.device_bank().to_bank()
    ref = ctl_jx.bank()
    for i in range(4):
        assert snap.row(i).as_points() == pytest.approx(ref.row(i).as_points())


def test_steady_state_carry_width_stays_bounded():
    """Regression: duplicate-x folds (a converged controller re-observing
    the same distribution every step) must not inflate the host-tracked
    count bound into endless padded-width doublings and jit recompiles."""
    with jax.enable_x64():
        ctl = BalanceController(n_units=64, num_groups=4, eps=0.05, backend="jax")
        speeds = [4.0, 4.0, 4.0, 2.0]
        for _ in range(60):
            times = [d / s for d, s in zip(ctl.d, speeds)]
            ctl.observe(times)
        carry = ctl._carry_bank()
        true_max = int(np.asarray(carry.counts).max())
        assert int(carry.xs.shape[-1]) <= max(2 * true_max, 8)


def test_dfpa_scalar_executor_jax_backend_small():
    """Cold-start growth path: the carry's padded width doubles as rounds
    accumulate points; semantics must not change across the re-pad."""
    ex = SimulatedExecutor(
        time_fns=[lambda x: x / 100.0, lambda x: x / 40.0, lambda x: x / 10.0]
    )
    with jax.enable_x64():
        res = dfpa(ex, 300, eps=0.02, min_units=1, backend="jax")
    assert sum(res.d) == 300
    assert res.converged
