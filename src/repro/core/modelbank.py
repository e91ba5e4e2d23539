"""Vectorized bank of piecewise-linear FPMs — the batched model core.

The paper's headline requirement is that the cost of computing an optimal
distribution must be *orders of magnitude* below the application time for
self-adaptability to pay off.  The scalar path (``fpm.PiecewiseLinearFPM`` +
``partition.partition_units``) evaluates ``alloc_at_time`` one processor at a
time in Python, so every bisection step on ``t*`` costs a ``p``-long Python
loop over per-model segment scans — fine for the paper's 15-node HCL cluster,
hopeless for fleets of thousands of device groups.

``ModelBank`` stores all ``p`` models as padded 2-D arrays:

  * ``xs[p, k_max]`` — sorted observed problem sizes, right-padded by
    repeating each row's last point (padding segments have zero length and
    are masked out);
  * ``ss[p, k_max]`` — the speeds at those points, padded the same way;
  * ``counts[p]``    — number of valid points per row (0 = empty model).

and evaluates the three model queries for the WHOLE bank in single numpy
passes:

  * ``speed(x)`` / ``time(x)`` — batched piecewise-linear interpolation with
    constant extension outside the observed range (identical semantics to
    ``PiecewiseLinearFPM.speed``/``time`` elementwise);
  * ``alloc_at_time(t, caps) -> [p]`` — the closed-form per-segment
    feasibility test ``x (1 - t m) <= t (s0 - m x0)`` evaluated for every
    segment of every processor at once.

The bank is the inner loop of the vectorized partitioners in
``partition.py``: one bisection step on ``t*`` becomes one ``total_alloc``
array op instead of ``p`` Python calls.  The scalar ``SpeedModel`` protocol
survives as a thin adapter (``row()`` / ``to_models()``), so existing call
sites keep working unchanged.

Three backends, one semantics
-----------------------------

Three implementations of the same partitioning algorithm coexist, and
``tests/test_modelbank_jax.py`` fuzz-locks them together:

* **scalar** (``fpm.py`` + the ``_scalar`` helpers in ``partition.py``) —
  one Python object per processor.  Selected automatically when a model has
  no piecewise representation (``AnalyticModel``: FFMPA baselines, oracle
  partitions over raw time functions) or explicitly with
  ``vectorize=False``.  This is the semantics reference: both banked paths
  mirror its closed-form per-segment feasibility test expression for
  expression.
* **numpy bank** (this module; the default, ``backend="numpy"``) — padded
  ``[p, k]`` arrays on the host, one numpy pass per bisection step, lazy-heap
  integer completion.  The right path for host-side control loops at any
  fleet size; no accelerator or warm-up required.
* **jax bank** (``modelbank_jax.py``, ``backend="jax"``) — the same padded
  layout as device arrays, the whole ``t*`` search and greedy completion
  under ``jax.jit`` (fixed-iteration ``lax.fori_loop`` bisection,
  masked-argmin completion), plus ``fold_in`` so DFPA and the
  ``BalanceController`` keep the bank as a device-resident carry across
  rounds.  Pick it when repartitioning must compose with a jitted training
  step or run at high frequency: after the one-time compile a repartition
  costs microseconds.  With x64 enabled its element-wise float ops are
  IEEE-identical to numpy's, and allocations match the numpy bank
  bit-for-bit; in float32 they may differ by a unit.

All three raise the same ``ValueError`` s on infeasible inputs (``sum(caps)
< n``, ``min_units * p > n``, any ``cap < min_units``, empty models with
positive caps), and all three produce allocations that sum exactly to ``n``
with identical makespans (tie-breaks may place a leftover unit differently
only between the scalar and banked continuous solvers' float paths).

**The two-level (hierarchical) path** composes the same three backends one
level up.  Given a ``groups[p]`` assignment, :func:`aggregate_groups` builds
one *group-level* speed function per group — the pointwise
sum-of-speeds-at-equal-time composition ``X_G(t) = sum_{i in G}
alloc_i(t, cap_i)``, sampled at the union of the members' knot times (plus
their cap-crossing times), so the aggregate is exact at every sampled knot
and piecewise-interpolated between them.  The aggregate is monotone-time BY
CONSTRUCTION (its knots are sampled at sorted times, so the segment
inequality ``x0 s1 <= x1 s0`` reduces to ``t0 <= t1``), which means the
threshold-count completion is always exact at the group level regardless of
the members' shapes.  ``core/hierarchy.py`` then solves the outer ``t*``
bisection over the ``[g, k_g]`` group bank — O(g k_g) instead of O(p k) —
and scatters each group's integer share to an inner per-group partition on
the group's own ``[p_g, k]`` sub-bank: per-group host solves on numpy,
one program over ``[g, p_max, k]`` blocks on jax (``_hier_inner_jit``:
small groups batched, large ones under ``lax.map``), and the same body ``shard_map``'d across devices
under ``sharding="shard_map"`` so no device touches more than its
``ceil(g/ndev)`` blocks.  One group reproduces the flat path bit-identically
(the outer trivially assigns it all ``n`` units and the inner IS the flat
kernel); multiple groups agree with the flat makespan to within the
interpolation error of the aggregate (fuzz-locked in
``tests/test_hierarchy.py``).

Time and energy, one bank layout
--------------------------------

The bi-objective extension (``core/energy.py``; ROADMAP direction 4) does
not add a fourth backend — it adds a SECOND bank in the same layout.  An
optional ``energy`` sub-bank (``es[p, k]``, attached with
:meth:`ModelBank.with_energy` / built by ``SpeedStore.attach_energy``)
stores per-processor *energy-rate* functions ``er_i(x) = x / E_i(x)``, so
``energy.time(x)`` IS the energy ``E_i(x)`` and every mechanism above —
padded layout, fold-in, stacking, monotone flags, the jitted ``t*``
bisection and threshold-count completion — serves the energy objective
verbatim.  ``energy_at(d)`` / ``fleet_energy(d)`` evaluate per-processor
and total energies of a distribution; ``objective="energy"`` partitions
run the SAME geometric kernel on the energy sub-bank (balancing
per-processor energies), and the makespan/energy Pareto front is a batched
sweep of time-threshold bisections — tightened caps
``min(cap_i, floor(alloc_time_i(t)))`` feeding stacked ``[T, p, k]``
energy solves (``energy.pareto_front``), numpy/jax bit-identical under the
same fuzz-parity regime as speed (``tests/test_energy.py``).

The fleet layer stacks the jax backend one level higher: q concurrent
jobs' banks live in ONE ``[q, p, k]`` ``JaxModelBank`` owned by
``repro.fleet.FleetScheduler`` (per-job ``n``/caps/``min_units`` and
per-lane completion routing ride the batch dims), so a whole fleet's
measurement round — or a ``Scheduler.partition_grid`` outer round, whose
per-column inner loops run through the same driver — is one device
program.  The stacked carry is derived state: the per-job scalar estimates
stay the source of truth, and the stack is rebuilt lazily when jobs come
and go.  ``repro.fleet.ProfileRegistry`` persists those estimates across
sessions keyed by ``(device_class, workload_tag)``.

Completion modes and the monotonicity contract
----------------------------------------------

The integer completion (placing the ``n - sum(floor(x_i))`` leftover units
after the continuous solve) has two implementations on the banked backends:

* **per-unit greedy** (``completion="greedy"``) — the semantics reference:
  each leftover unit goes to the processor minimizing
  ``(time(d_i + 1), -frac_remainder, index)``; a lazy heap on the numpy
  bank, a masked lexicographic-argmin ``while_loop`` on the jax bank.
  Exact for ANY speed estimate, but sequential: ~``p/2`` iterations.
* **threshold-count** (``completion="threshold"``) — for *monotone-time*
  banks only: when every row's per-unit time ``x / s_i(x)`` is nondecreasing
  in ``x``, the greedy processes unit increments in globally sorted
  ``(time, -rem, index)`` order, so the optimal completion collapses to one
  more bisection — count units under a candidate time threshold ``t`` via
  ``floor(alloc_at_time(t))`` (clamped to ``[d_i, cap_i]``), bisect ``t``
  until ``count(lo) < leftover <= count(hi)``, bulk-grant everything
  counted at ``lo``, and resolve only the handful of boundary-tied units
  with the exact greedy.  One ``O(p k)`` pass per bisection step instead of
  one ``O(p)`` argmin per unit — this is what makes ``p = 10^5`` fleets
  repartition in milliseconds, and because the boundary remainder runs
  through the *same* greedy, makespans (and in practice allocations) are
  bit-identical to the per-unit path.
* **auto** (the default) — backend-aware: on the *jitted* backends it picks
  threshold-count iff the bank's ``monotone`` flag holds (per-unit greedy
  otherwise), because the per-unit ``while_loop``'s serial dispatch was the
  p=10^4..10^5 bottleneck there; on the *numpy host* path it always keeps
  the lazy heap — the heap was never the host bottleneck, and the threshold
  pass costs ~one extra continuous solve (``bank_threshold_s`` vs
  ``bank_s`` in ``BENCH_partition.json`` records the tradeoff).  The flag
  is a host-side ``O(p k)`` check recorded lazily on the bank: time is
  nondecreasing on a linear segment iff its knot times are ordered
  (``x0 * s1 <= x1 * s0``), so a row is monotone iff its knots are sorted,
  its speeds positive and finite, and every consecutive knot pair satisfies
  that inequality.  Adversarial (non-monotone) banks — speed spikes,
  duplicate-``x`` rows whose replacing speed jumps up — are provably
  demoted to the exact per-unit loop (``tests/test_completion.py``); on a
  stacked ``[q, p, k]`` bank the routing is *per column*
  (``JaxModelBank.monotone_lanes``), so an adversarial column demotes only
  itself.  Forcing ``completion="threshold"`` on a non-monotone bank is a
  benchmark-only override with no exactness guarantee.

The scalar backend always runs its per-unit loop (asking it for
``"threshold"`` raises ``ValueError``).

Migration: free functions → Scheduler
-------------------------------------

The backend used to be chosen per call (``vectorize=`` / ``backend=``
kwargs, re-derived by ``_as_bank``-style dispatch helpers at every entry
point).  It is now chosen ONCE, at ``SpeedStore`` construction, and the
lifecycle lives on the ``Scheduler`` facade (``core/scheduler.py``).  The
old entry points still work but emit ``DeprecationWarning`` and delegate:

======================================================  =====================================================
legacy                                                  facade
======================================================  =====================================================
``partition_units(models, n, backend="jax")``           ``SpeedStore.from_models(models, backend="jax")``
                                                        ``    .partition_units(n)``
``partition_units(models, n, vectorize=False)``         ``SpeedStore.from_models(models, backend="scalar")``
``partition_continuous(models, n)``                     ``store.partition_continuous(n)``
(per-unit greedy completion, always)                    ``store.partition_units(n, completion=...)``
                                                        (``"auto"`` routes monotone banks to the
                                                        threshold-count completion)
(float64 device bank, always)                           ``SpeedStore.from_models(models, backend="jax",``
                                                        ``    dtype=np.float32)``
``cpm_partition(speeds, n)``                            ``Scheduler.from_speeds(speeds).partition(n)``
``dfpa(executor, n, eps, ...)``                         ``Scheduler().autotune(executor, n, eps, ...)``
``dfpa_partition_2d(grid, M, N, eps)``                  ``Scheduler(grid=grid, policy=Policy.GRID2D)``
                                                        ``    .partition_grid(M, N, eps=eps)``
``cpm_partition_2d`` / ``ffmpa_partition_2d``           same, with ``policy=Policy.CPM`` / ``Policy.FFMPA``
``bank_repartition_2d(fpms, widths, M)``                ``Scheduler(...).repartition_grid(...)``
``BalanceController(...).observe(times)``               ``Scheduler(n_units=..., num_groups=...)``
                                                        ``    .observe(times)``
``elastic_rebalance(ctrl, surviving, joined)``          ``sched.resize(...)`` / ``sched.join()`` /
                                                        ``sched.leave(g)``
``StragglerDetector`` wiring + ``det.reprofile``        ``sched.straggler_actions(times)`` (auto-reprofiles)
``ctrl.state_dict()`` (lost backend/smooth)             ``sched.state_dict()`` (full config round-trips)
(no energy objective)                                   ``sched.partition(n, objective="time"|"energy"``
                                                        ``    |"pareto", energy_cap=...)`` after
                                                        ``sched.attach_energy(energy_models)``
======================================================  =====================================================

Results are a typed ``Partition`` (allocations, ``t_star``, makespan,
imbalance, convergence, per-group diagnostics) instead of bare lists /
``DFPAResult`` / ``Grid2DResult``.  ``AnalyticModel`` consumers that want
the banked paths can sample-and-bank via
``SpeedStore.from_models(..., analytic_tol=..., analytic_hi=n)``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, NamedTuple, Optional, Sequence, Tuple, Union

import numpy as np

from .fpm import ConstantModel, PiecewiseLinearFPM

__all__ = ["GroupIndex", "ModelBank", "aggregate_groups", "group_index", "group_members"]

ArrayLike = Union[float, Sequence[float], np.ndarray]


def _monotone_rows(xs: np.ndarray, ss: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Per-row monotone-time flags ``[..., p]`` of a padded bank (see
    :meth:`ModelBank.is_monotone`): knots positive and finite, knot times
    ``x/s`` nondecreasing."""
    k = xs.shape[-1]
    pts = np.arange(k) < counts[..., None]
    ok_pts = (xs > 0.0) & np.isfinite(xs) & (ss > 0.0) & np.isfinite(ss)
    ok = ~np.any(pts & ~ok_pts, axis=-1)
    if k >= 2:
        x0, x1 = xs[..., :-1], xs[..., 1:]
        s0, s1 = ss[..., :-1], ss[..., 1:]
        seg = np.arange(k - 1) < (counts - 1)[..., None]
        ok_seg = (x1 >= x0) & (x0 * s1 <= x1 * s0)
        ok &= ~np.any(seg & ~ok_seg, axis=-1)
    return ok


def _monotone_check(xs: np.ndarray, ss: np.ndarray, counts: np.ndarray) -> bool:
    """Host-side monotone-time check over a padded bank (see
    :meth:`ModelBank.is_monotone`); one numpy pass, shared with the jax
    bank's host snapshot path."""
    return bool(np.all(_monotone_rows(xs, ss, counts)))


@dataclass
class ModelBank:
    """All ``p`` piecewise-linear FPMs as padded arrays (see module docstring)."""

    xs: np.ndarray  # [p, k_max] float64, row-sorted, padding repeats last point
    ss: np.ndarray  # [p, k_max] float64, padded the same way
    counts: np.ndarray  # [p] int64, number of valid points per row
    # Host-side monotone-time flag (None = unknown, computed lazily by
    # is_monotone()); routes the threshold-count integer completion.
    monotone: Optional[bool] = None
    # Optional energy sub-bank (same layout; ss holds energy RATES x/E(x),
    # so energy.time(x) == E(x)) — see the "time and energy" docstring
    # section and core/energy.py.
    energy: Optional["ModelBank"] = None

    # -- construction --------------------------------------------------------

    @classmethod
    def from_point_lists(
        cls, points: Sequence[Tuple[Sequence[float], Sequence[float]]]
    ) -> "ModelBank":
        """Build from per-processor ``(xs_i, ss_i)`` sorted point lists."""
        p = len(points)
        counts = np.array([len(px) for px, _ in points], dtype=np.int64)
        k_max = max(int(counts.max(initial=1)), 1)
        xs = np.zeros((p, k_max), dtype=np.float64)
        ss = np.zeros((p, k_max), dtype=np.float64)
        for i, (px, ps) in enumerate(points):
            c = len(px)
            if c == 0:
                continue
            xs[i, :c] = px
            ss[i, :c] = ps
            xs[i, c:] = px[-1]  # zero-length padding segments, masked later
            ss[i, c:] = ps[-1]
        return cls(xs=xs, ss=ss, counts=counts)

    @classmethod
    def from_models(cls, models: Sequence[object]) -> "ModelBank":
        """Adapt a sequence of scalar models into a bank.

        Accepts ``PiecewiseLinearFPM``, ``ConstantModel`` (becomes the
        single-point model ``{(1, s)}``, whose constant extension reproduces
        it exactly), and anything exposing ``as_points()``.  Raises
        ``TypeError`` for models with no piecewise representation (e.g.
        ``AnalyticModel``) — callers fall back to the scalar path.
        """
        pts: List[Tuple[List[float], List[float]]] = []
        for m in models:
            if isinstance(m, PiecewiseLinearFPM):
                pts.append((list(m.xs), list(m.ss)))
            elif isinstance(m, ConstantModel):
                pts.append(([1.0], [float(m.s)]))
            elif hasattr(m, "as_points"):
                pp = m.as_points()
                pts.append(([float(x) for x, _ in pp], [float(s) for _, s in pp]))
            else:
                raise TypeError(
                    f"{type(m).__name__} has no piecewise representation; "
                    "use the scalar partition path"
                )
        return cls.from_point_lists(pts)

    # -- shape ---------------------------------------------------------------

    @property
    def p(self) -> int:
        return self.xs.shape[0]

    def __len__(self) -> int:
        return self.p

    @property
    def num_points(self) -> np.ndarray:
        return self.counts

    # -- monotonicity (threshold-count completion routing) -------------------

    def is_monotone(self) -> bool:
        """True iff every row's time ``x / s_i(x)`` is nondecreasing on
        ``[0, inf)`` — the contract under which the threshold-count integer
        completion is exact (see the module docstring).

        On a linear segment ``s(x) = s0 + m (x - x0)`` the time derivative
        has the constant sign of ``s0 x1 - s1 x0``, so the whole row is
        monotone iff its knots are sorted, its speeds positive and finite,
        and the knot times ``x/s`` are nondecreasing (``x0 s1 <= x1 s0``).
        The constant extensions outside the observed range are always
        increasing.  Rows with non-positive / non-finite points (possible
        only in hand-built banks) demote the bank conservatively.  Computed
        once per bank, ``O(p k)``, and cached on the ``monotone`` field.
        """
        if self.monotone is None:
            self.monotone = _monotone_check(self.xs, self.ss, self.counts)
        return self.monotone

    # -- batched evaluation --------------------------------------------------

    def _edges(self):
        idx = np.arange(self.p)
        last = np.maximum(self.counts - 1, 0)
        return self.xs[idx, 0], self.ss[idx, 0], self.xs[idx, last], self.ss[idx, last]

    def speed(self, x: ArrayLike) -> np.ndarray:
        """Batched ``s_i(x_i)``; ``x`` is a scalar or a ``[p]`` vector.

        Empty rows evaluate to NaN (the scalar model raises there).
        """
        x = np.broadcast_to(np.asarray(x, dtype=np.float64), (self.p,))
        first_x, first_s, last_x, last_s = self._edges()
        # k = bisect_right(xs, x) - 1, batched; padding repeats last_x so it
        # never out-counts an interior x.
        k = np.sum(self.xs <= x[:, None], axis=1) - 1
        k = np.clip(k, 0, np.maximum(self.counts - 2, 0))
        idx = np.arange(self.p)
        kp1 = np.minimum(k + 1, self.xs.shape[1] - 1)
        x0, x1 = self.xs[idx, k], self.xs[idx, kp1]
        s0, s1 = self.ss[idx, k], self.ss[idx, kp1]
        denom = np.where(x1 > x0, x1 - x0, 1.0)
        w = (x - x0) / denom
        interior = s0 + w * (s1 - s0)
        s = np.where(x <= first_x, first_s, np.where(x >= last_x, last_s, interior))
        return np.where(self.counts > 0, s, np.nan)

    def time(self, x: ArrayLike) -> np.ndarray:
        """Batched ``t_i(x_i) = x_i / s_i(x_i)`` (0 for non-positive ``x``)."""
        x = np.broadcast_to(np.asarray(x, dtype=np.float64), (self.p,))
        with np.errstate(divide="ignore", invalid="ignore"):
            t = x / self.speed(x)
        return np.where(x > 0.0, t, 0.0)

    def alloc_at_time(self, t: float, caps: ArrayLike) -> np.ndarray:
        """Batched ``max { x in [0, cap_i] : x / s_i(x) <= t }`` -> ``[p]``.

        One numpy pass over every segment of every processor — the closed-form
        linear-inequality test of ``PiecewiseLinearFPM.alloc_at_time``,
        elementwise identical to the scalar implementation.
        """
        caps = np.broadcast_to(np.asarray(caps, dtype=np.float64), (self.p,))
        if t <= 0.0:
            return np.zeros(self.p, dtype=np.float64)
        first_x, first_s, last_x, last_s = self._edges()

        # Region [0, x_1]: constant speed ss[:, 0].
        best = np.minimum(t * first_s, np.minimum(first_x, caps))

        # Interior segments, all at once: s(x) = s0 + m (x - x0) on [x0, x1];
        # x <= t s(x)  <=>  x (1 - t m) <= t (s0 - m x0).
        k_max = self.xs.shape[1]
        if k_max >= 2:
            x0, x1 = self.xs[:, :-1], self.xs[:, 1:]
            s0, s1 = self.ss[:, :-1], self.ss[:, 1:]
            seg = np.arange(k_max - 1)[None, :]
            valid = (
                (seg < (self.counts - 1)[:, None])
                & (x0 < caps[:, None])
                & (x1 > x0)
            )
            x1c = np.minimum(x1, caps[:, None])
            denom = np.where(x1 > x0, x1 - x0, 1.0)
            m = (s1 - s0) / denom
            a = 1.0 - t * m
            b = t * (s0 - m * x0)
            with np.errstate(divide="ignore", invalid="ignore"):
                ub = b / np.where(a != 0.0, a, 1.0)
            cand = np.where(
                a > 0.0,
                np.where(ub >= x0, np.minimum(ub, x1c), 0.0),
                np.where(
                    a == 0.0,
                    np.where(b >= 0.0, x1c, 0.0),
                    np.where(x1c >= ub, x1c, 0.0),
                ),
            )
            cand = np.where(valid, cand, 0.0)
            best = np.maximum(best, cand.max(axis=1))

        # Region [x_m, cap]: constant speed ss[:, count-1].
        ub_r = t * last_s
        right = (caps > last_x) & (ub_r >= last_x) & (self.counts > 0)
        best = np.maximum(best, np.where(right, np.minimum(ub_r, caps), 0.0))

        return np.where((caps > 0.0) & (self.counts > 0), best, 0.0)

    def total_alloc(self, t: float, caps: ArrayLike) -> float:
        """``sum_i alloc_i(t)`` — one bisection step of the partitioner."""
        return float(self.alloc_at_time(t, caps).sum())

    # -- scalar access (greedy completion, adapters) -------------------------

    def speed_one(self, i: int, x: float) -> float:
        """Scalar ``s_i(x)`` for one row (used by the greedy unit completion)."""
        c = int(self.counts[i])
        if c == 0:
            raise ValueError("empty FPM row")
        xs, ss = self.xs[i], self.ss[i]
        if x <= xs[0]:
            return float(ss[0])
        if x >= xs[c - 1]:
            return float(ss[c - 1])
        k = int(np.searchsorted(xs[:c], x, side="right")) - 1
        w = (x - xs[k]) / (xs[k + 1] - xs[k])
        return float(ss[k] + w * (ss[k + 1] - ss[k]))

    def time_one(self, i: int, x: float) -> float:
        if x <= 0.0:
            return 0.0
        return x / self.speed_one(i, x)

    # -- the energy sub-bank (core/energy.py) --------------------------------

    def with_energy(self, energy: "ModelBank") -> "ModelBank":
        """Attach an energy sub-bank (same ``p``; ``ss`` holds energy rates
        ``x / E(x)``) — returns a new bank sharing this bank's arrays."""
        if energy.p != self.p:
            raise ValueError(
                f"energy bank has {energy.p} rows but speed bank has {self.p}"
            )
        return ModelBank(
            xs=self.xs, ss=self.ss, counts=self.counts,
            monotone=self.monotone, energy=energy,
        )

    def energy_at(self, d: ArrayLike) -> np.ndarray:
        """Per-processor energies ``E_i(d_i)`` of a distribution (0 for
        ``d_i <= 0``, NaN on empty energy rows with units)."""
        if self.energy is None:
            raise ValueError("no energy sub-bank attached (use with_energy)")
        return self.energy.time(d)

    def fleet_energy(self, d: ArrayLike) -> float:
        """Total fleet energy ``sum_i E_i(d_i)`` of a distribution."""
        return float(self.energy_at(d).sum())

    # -- transformations -----------------------------------------------------

    def scaled(self, speed_scale: ArrayLike) -> "ModelBank":
        """New bank with every row's speeds multiplied by ``speed_scale[i]``
        (the 2-D partitioner's column-width rescaling, batched).  A uniform
        positive per-row scale preserves time-monotonicity, so the cached
        flag carries over; any other scale resets it to unknown.  The energy
        sub-bank (problem-size semantics unchanged by a speed rescale)
        carries through untouched."""
        scale = np.broadcast_to(np.asarray(speed_scale, dtype=np.float64), (self.p,))
        return ModelBank(
            xs=self.xs.copy(),
            ss=self.ss * scale[:, None],
            counts=self.counts.copy(),
            monotone=self.monotone if bool(np.all(scale > 0.0)) else None,
            energy=self.energy,
        )

    # -- adapters back to the scalar protocol --------------------------------

    def row(self, i: int) -> PiecewiseLinearFPM:
        """Scalar ``SpeedModel`` view of one processor."""
        c = int(self.counts[i])
        return PiecewiseLinearFPM(xs=list(self.xs[i, :c]), ss=list(self.ss[i, :c]))

    def to_models(self) -> List[PiecewiseLinearFPM]:
        return [self.row(i) for i in range(self.p)]


# ---------------------------------------------------------------------------
# Group aggregation — the two-level partitioning path (core/hierarchy.py)
#
# Everything here works on all groups at once: a group is a row of a
# ``[g, p_max]`` member-index block (``GroupIndex``), sample times are
# ``[g, T]`` rows padded with ``inf``, and the member allocations are
# ``[g, T, p_max]`` slabs.  Each group's row is bitwise what the same
# expressions give for that group alone.
# ---------------------------------------------------------------------------


class GroupIndex(NamedTuple):
    """A ``groups[p]`` assignment as arrays: group ``r`` (the ``r``-th
    smallest id) holds the processors ``idx[r, :sizes[r]]``, ascending."""

    gids: np.ndarray  # [g] sorted unique group ids
    idx: np.ndarray  # [g, p_max] member processor indices, 0 where padded
    mask: np.ndarray  # [g, p_max] True on members
    sizes: np.ndarray  # [g]

    @property
    def g(self) -> int:
        return int(self.sizes.shape[0])

    @property
    def p(self) -> int:
        return int(self.sizes.sum())

    @property
    def members(self) -> List[np.ndarray]:
        return [row[:s] for row, s in zip(self.idx, self.sizes.tolist())]

    def gather(self, a: np.ndarray, fill=0) -> np.ndarray:
        """``a[p, ...]`` as member blocks ``[g, p_max, ...]``, ``fill`` on
        padded rows."""
        out = a[self.idx]
        mask = self.mask.reshape(self.mask.shape + (1,) * (a.ndim - 1))
        return np.where(mask, out, np.asarray(fill, dtype=out.dtype))

    def scatter(self, blocks: np.ndarray, p: int) -> np.ndarray:
        """Member blocks ``[g, p_max]`` back to a ``[p]`` vector."""
        out = np.zeros(p, dtype=blocks.dtype)
        out[self.idx[self.mask]] = blocks[self.mask]
        return out


def group_index(groups: Sequence[int]) -> GroupIndex:
    """Normalize a ``groups[p]`` assignment into member blocks."""
    garr = np.asarray(groups)
    if garr.ndim != 1:
        raise ValueError("groups must be a 1-D per-processor assignment")
    gids, inv = np.unique(garr, return_inverse=True)
    g = int(gids.shape[0])
    sizes = np.bincount(inv, minlength=g).astype(np.int64)
    order = np.argsort(inv, kind="stable")  # ascending members per group
    starts = np.cumsum(sizes) - sizes
    pos = np.arange(garr.shape[0]) - starts[inv[order]]
    p_max = max(int(sizes.max(initial=0)), 1)
    idx = np.zeros((g, p_max), dtype=np.int64)
    mask = np.zeros((g, p_max), dtype=bool)
    idx[inv[order], pos] = order
    mask[inv[order], pos] = True
    return GroupIndex(gids.astype(np.int64), idx, mask, sizes)


def group_members(groups: Sequence[int]) -> Tuple[List[int], List[np.ndarray]]:
    """Normalize a ``groups[p]`` assignment: returns the sorted unique group
    ids and, per group, the member processor indices in ascending order (the
    order the hierarchical scatter preserves)."""
    index = group_index(groups)
    return index.gids.tolist(), index.members


def _alloc_at_times_blocks(
    xs: np.ndarray, ss: np.ndarray, counts: np.ndarray, caps: np.ndarray,
    ts: np.ndarray,
) -> np.ndarray:
    """``alloc_at_time`` of ``[G, P, k]`` member blocks at per-group sample
    times ``ts[G, T]``: returns ``[G, T, P]``.  Expression-for-expression
    :meth:`ModelBank.alloc_at_time` with leading group and time axes, so each
    element is bitwise what the single-row call gives."""
    ts = np.asarray(ts, dtype=np.float64)[..., None]  # [G, T, 1]
    caps2 = caps[:, None, :]  # [G, 1, P]
    last = np.maximum(counts - 1, 0)
    first_x, first_s = xs[:, None, :, 0], ss[:, None, :, 0]
    last_x = np.take_along_axis(xs, last[..., None], axis=-1)[:, None, :, 0]
    last_s = np.take_along_axis(ss, last[..., None], axis=-1)[:, None, :, 0]

    best = np.minimum(ts * first_s, np.minimum(first_x, caps2))

    k_max = xs.shape[-1]
    if k_max >= 2:
        x0, x1 = xs[:, None, :, :-1], xs[:, None, :, 1:]
        s0, s1 = ss[:, None, :, :-1], ss[:, None, :, 1:]
        seg = np.arange(k_max - 1)
        valid = (
            (seg < (counts - 1)[:, None, :, None])
            & (x0 < caps2[..., None])
            & (x1 > x0)
        )
        x1c = np.minimum(x1, caps2[..., None])
        denom = np.where(x1 > x0, x1 - x0, 1.0)
        m = (s1 - s0) / denom
        tseg = ts[..., None]  # [G, T, 1, 1]
        a = 1.0 - tseg * m
        b = tseg * (s0 - m * x0)
        with np.errstate(divide="ignore", invalid="ignore"):
            ub = b / np.where(a != 0.0, a, 1.0)
        cand = np.where(
            a > 0.0,
            np.where(ub >= x0, np.minimum(ub, x1c), 0.0),
            np.where(
                a == 0.0,
                np.where(b >= 0.0, x1c, 0.0),
                np.where(x1c >= ub, x1c, 0.0),
            ),
        )
        cand = np.where(valid, cand, 0.0)
        best = np.maximum(best, cand.max(axis=-1))

    ub_r = ts * last_s
    nonempty = (counts > 0)[:, None, :]
    right = (caps2 > last_x) & (ub_r >= last_x) & nonempty
    best = np.maximum(best, np.where(right, np.minimum(ub_r, caps2), 0.0))

    best = np.where((caps2 > 0.0) & nonempty, best, 0.0)
    return np.where(ts > 0.0, best, 0.0)


def _alloc_at_times(bank: ModelBank, ts: np.ndarray, caps: np.ndarray) -> np.ndarray:
    """``alloc_at_time`` of a whole bank at a vector of times: ``[T, p]``."""
    caps = np.broadcast_to(np.asarray(caps, dtype=np.float64), (bank.p,))
    return _alloc_at_times_blocks(
        bank.xs[None], bank.ss[None], bank.counts[None], caps[None],
        np.asarray(ts, dtype=np.float64)[None],
    )[0]


def sum_members(a: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """Sum the member axis (last) of ``[G, ..., p_max]`` blocks over each
    group's ``sizes[r]`` members.  Groups of one size are summed together
    as a contiguous ``[..., size]`` array, so each group's sum runs in the
    same order as a sum over that group alone."""
    out = np.zeros(a.shape[:-1], dtype=a.dtype)
    for s in np.unique(sizes).tolist():
        if s == 0:
            continue
        rows = np.flatnonzero(sizes == s)
        out[rows] = np.ascontiguousarray(a[rows, ..., :s]).sum(axis=-1)
    return out


def _row_unique(a: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Row-wise ``np.unique`` of ``[r, m]`` values whose absent entries are
    ``inf``: sorted rows (``inf`` padded) and the count kept per row."""
    a = np.sort(a, axis=1)
    if a.shape[1] > 1:
        dup = np.zeros(a.shape, dtype=bool)
        dup[:, 1:] = a[:, 1:] == a[:, :-1]
        a = np.sort(np.where(dup, np.inf, a), axis=1)
    return a, np.isfinite(a).sum(axis=1)


def _linspace_rows(start: np.ndarray, stop: np.ndarray, num: int) -> np.ndarray:
    """``np.linspace(start[r], stop[r], num)`` for every row ``r``, in the
    scalar call's own arithmetic (``num >= 2``)."""
    div = num - 1
    delta = stop - start
    step = delta / div
    y = np.arange(0, num, dtype=np.float64)[None, :]
    y = np.where((step == 0)[:, None], (y / div) * delta[:, None], y * step[:, None])
    y = y + start[:, None]
    y[:, -1] = stop
    return y


def _geomspace_rows(start: np.ndarray, stop: np.ndarray, num: int) -> np.ndarray:
    """``np.geomspace(start[r], stop[r], num)`` for positive rows, in the
    scalar call's own arithmetic (``num >= 2``)."""
    y = np.power(10.0, _linspace_rows(np.log10(start), np.log10(stop), num))
    y[:, 0] = start
    y[:, -1] = stop
    return y


def aggregate_width(max_knots: int) -> int:
    """The most knots one group's aggregate can have: ``quota`` sampled
    times, a bracket below each, and ``quota`` geometric fills."""
    return 3 * max(max_knots // 4, 2)


def aggregate_times(
    bank: ModelBank, index: GroupIndex, caps: np.ndarray, max_knots: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Sample times of every group's aggregate (see :func:`aggregate_groups`):
    ``(ts [g, aggregate_width(max_knots)], count [g])``, each row sorted,
    strictly positive and right-padded by repeating its last time (1.0 on
    a row with none), so every padded column evaluates to a sample that is
    then dropped.

    A group's times are the distinct finite positive knot times ``x/s`` of
    its members with a positive cap, plus each such member's cap-crossing
    time ``time_i(cap_i)``; above ``quota = max(max_knots // 4, 2)`` of
    them, ``quota`` are kept at the rounded positions of
    ``linspace(0, count - 1, quota)``; each kept time is joined by
    ``t (1 - 1e-9)``, just below it; and when the row spans an interval,
    ``geomspace(first, last, quota)`` is added.
    """
    g = index.g
    width = aggregate_width(max_knots)
    if g == 0:
        return np.ones((0, width)), np.zeros(0, dtype=np.int64)
    quota = max(max_knots // 4, 2)
    caps = np.asarray(caps, dtype=np.float64)
    kc = max(int(bank.counts.max(initial=0)), 1)
    xs, ss = bank.xs[:, :kc], bank.ss[:, :kc]
    with np.errstate(divide="ignore", invalid="ignore"):
        t_pts = np.where(ss > 0, xs / ss, np.nan)
    valid = (np.arange(kc)[None, :] < bank.counts[:, None]) & (caps[:, None] > 0)
    active = (caps > 0) & (bank.counts > 0)
    with np.errstate(divide="ignore", invalid="ignore"):
        cap_t = bank.time(np.where(active, caps, 1.0))
    cand = np.concatenate(
        [np.where(valid, t_pts, np.nan), np.where(active, cap_t, np.nan)[:, None]], axis=1
    )
    cand = np.where(np.isfinite(cand) & (cand > 0), cand, np.inf)
    ts, cnt = _row_unique(index.gather(cand, np.inf).reshape(g, -1))
    ts = ts[:, : max(int(cnt.max(initial=0)), quota)]

    big = np.flatnonzero(cnt > quota)
    if big.size:
        lin = _linspace_rows(np.zeros(big.size), (cnt[big] - 1).astype(np.float64), quota)
        pick = np.round(lin).astype(int)
        ts[big, :quota] = np.take_along_axis(ts[big], pick, axis=1)
    ts = ts[:, :quota]
    cnt = np.minimum(cnt, quota)

    ts, cnt = _row_unique(np.concatenate([ts, ts * (1.0 - 1e-9)], axis=1))
    rows = np.arange(g)
    first, last = ts[:, 0], ts[rows, np.maximum(cnt - 1, 0)]
    fill = np.flatnonzero((cnt > 0) & (last > first))
    geo = np.full((g, quota), np.inf)
    if fill.size:
        geo[fill] = _geomspace_rows(first[fill], last[fill], quota)
    ts, cnt = _row_unique(np.concatenate([ts, geo], axis=1))
    ts = np.concatenate([ts, np.full((g, width), np.inf)], axis=1)[:, :width]
    pad = np.where(cnt > 0, ts[rows, np.maximum(cnt - 1, 0)], 1.0)
    ts = np.where(np.arange(width)[None, :] < cnt[:, None], ts, pad[:, None])
    return ts, cnt.astype(np.int64)


def aggregate_allocs_host(
    bank: ModelBank, index: GroupIndex, caps: np.ndarray, ts: np.ndarray
) -> np.ndarray:
    """Each group's summed member allocation at its sample times, ``[g, T]``.

    The pass materializes ~a dozen ``[G, T, P, k-1]`` temporaries, so it runs
    in slabs of ~1 MB: as many whole groups as fit, or, for a group too
    large for one slab, a few of its sample times at a time.  Slab edges
    change no element's arithmetic."""
    g, width = ts.shape
    p_max = index.idx.shape[1]
    k = bank.xs.shape[1]
    budget = 131_072
    g_chunk = max(1, budget // max(width * p_max * k, 1))
    t_chunk = width if g_chunk > 1 else max(1, budget // max(p_max * k, 1))
    caps = np.asarray(caps, dtype=np.float64)
    out = np.zeros((g, width), dtype=np.float64)
    for g0 in range(0, g, g_chunk):
        sl = slice(g0, min(g0 + g_chunk, g))
        sub = GroupIndex(index.gids[sl], index.idx[sl], index.mask[sl], index.sizes[sl])
        xs_b, ss_b = sub.gather(bank.xs), sub.gather(bank.ss)
        counts_b, caps_b = sub.gather(bank.counts), sub.gather(caps)
        for t0 in range(0, width, t_chunk):
            tl = slice(t0, min(t0 + t_chunk, width))
            alloc = _alloc_at_times_blocks(xs_b, ss_b, counts_b, caps_b, ts[sl, tl])
            out[sl, tl] = sum_members(alloc, sub.sizes)
    return out


def aggregate_bank(ts: np.ndarray, count: np.ndarray, xsum: np.ndarray) -> ModelBank:
    """The ``[g, k_g]`` group bank from sampled ``(time, aggregate size)``
    rows: a sample is a knot where the aggregate is positive and larger
    than at the previous sample (on an equal-size plateau, where every
    member is capped, the first and fastest one is kept); its speed is
    size over time.  Monotone-time by construction."""
    g, width = ts.shape
    keep = (np.arange(width)[None, :] < count[:, None]) & (xsum > 0)
    if width > 1:
        keep[:, 1:] &= (xsum[:, 1:] - xsum[:, :-1]) > 0
    counts = keep.sum(axis=1).astype(np.int64)
    k = max(int(counts.max(initial=1)), 1)
    order = np.argsort(~keep, axis=1, kind="stable")[:, :k]
    xk = np.take_along_axis(xsum, order, axis=1)
    tk = np.take_along_axis(ts, order, axis=1)
    last = np.maximum(counts - 1, 0)[:, None]
    col = np.arange(k)[None, :]
    xk = np.where(col < counts[:, None], xk, np.take_along_axis(xk, last, axis=1))
    tk = np.where(col < counts[:, None], tk, np.take_along_axis(tk, last, axis=1))
    empty = (counts == 0)[:, None]
    xs = np.where(empty, 0.0, xk)
    ss = np.where(empty, 0.0, xk / tk)
    return ModelBank(xs=xs, ss=ss, counts=counts, monotone=True)


def aggregate_groups(
    bank: ModelBank,
    groups: Sequence[int],
    caps: Sequence[float],
    *,
    max_group_knots: int = 64,
) -> Tuple[ModelBank, np.ndarray, List[np.ndarray]]:
    """Build the ``[g, k_g]`` group-level bank for a ``groups[p]`` assignment.

    A group's aggregate problem-size-at-time function is ``X(t) = sum_i
    alloc_i(t, cap_i)`` — exactly what one bisection step of the outer
    partitioner needs.  It is sampled at the times of
    :func:`aggregate_times`: the members' knot times ``x_ij / s_ij`` and
    cap-crossing times ``time_i(cap_i)`` (where a member's alloc saturates),
    so the aggregate is exact at every time any member's behaviour changes
    slope; between knots the bank's linear-in-speed interpolation
    approximates the true piecewise-rational composition.  Member caps are
    baked in (NOT the job size ``n``: the same aggregate serves any ``n``).
    Sampling at sorted times makes the result monotone-time by
    construction: ``x0 s1 <= x1 s0`` with ``s = x/t`` reduces to
    ``t0 <= t1``.

    Two refinements keep the interpolation honest between knot times:

    * a member's alloc can JUMP at a knot time — within a segment the
      implied time ``x / s(x)`` is a monotone hyperbola piece (``s``
      linear), so when it runs *decreasing* the whole segment becomes
      feasible the moment ``t`` reaches the far knot's time: a step, never
      an interior extremum.  A sample exactly at the knot time lands on TOP
      of that step; sampling each kept time again just below
      (``t (1 - 1e-9)``) pins the step's bottom;
    * between WIDELY separated knot times the sum of member pieces bends far
      from the interpolation, so a geometric fill of sample times spans the
      whole knot range.

    Returns ``(group_bank, group_caps, members)``: one aggregate row per
    group (``max_group_knots`` bounds each row's knot count through the
    quota, keeping the outer solve O(g k_g)), the summed member caps, and
    the per-group member indices.  Groups with no capacity get an empty
    row and cap 0 (the outer solver allocates them nothing).
    """
    caps_arr = np.broadcast_to(np.asarray(caps, dtype=np.float64), (bank.p,))
    index = group_index(groups)
    ts, count = aggregate_times(bank, index, caps_arr, max_group_knots)
    gbank = aggregate_bank(
        ts, count, aggregate_allocs_host(bank, index, caps_arr, ts)
    )
    gcaps = sum_members(index.gather(caps_arr), index.sizes)
    return gbank, gcaps, index.members
