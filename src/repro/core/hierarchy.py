"""Two-level hierarchical partitioning — outer solve over group aggregates,
inner per-group solves on each group's own sub-bank.

The paper's platforms are *hierarchically* heterogeneous — hosts grouped by
class, GPUs and CPU sockets grouped by node, groups behind a shared
interconnect — and the two-level partition follows that structure:

1. **Aggregate** each group behind a composite performance model
   (``aggregate_groups`` in ``modelbank.py``): the exact
   sum-of-allocs-at-equal-time composition sampled at the members' knot and
   cap-crossing times, a ``[g, k_g]`` bank that is monotone-time by
   construction.
2. **Outer solve**: the continuous ``t*`` bisection on the group bank —
   ``O(g k_g)`` per step — then floor at each group's ``min_units`` floor,
   clip at its summed caps, take back any overshoot, and grant the leftover
   units one at a time to the group with the smallest
   ``(time(share + 1), -frac_remainder, index)``, so the integer group
   shares sum to exactly ``n``.
3. **Inner solves**: each group's share is partitioned over its members on
   the group's ``[p_g, k]`` sub-bank by the flat partitioner.

Every step of the outer level works on all groups at once: member blocks
``[g, p_max]`` (``GroupIndex``), sample times ``[g, T]``, one aggregate bank,
and a completion that picks the granted units in bulk.  On the numpy backend
the continuous outer solve and the inner solves run on the host (one flat
solve per group).  On the jax backend the member allocations of the
aggregation, the continuous outer solve (the flat partitioner's
``_partition_continuous_jit`` on the aggregate bank, padded to
``aggregate_width`` knots so it compiles once) and all inner solves (one
program over ``[g, p_max, k]`` blocks, ``_hier_inner_jit``) run on the
device; under ``sharding="shard_map"`` the inner program runs per device over
its local group lanes, so no single device ever materializes more than
``ceil(g/ndev)`` blocks of the bank (``max_shard_elems``).  Built with
:meth:`Hierarchy.from_device_bank`, the blocks are gathered on the device
from a ``SpeedStore``'s carry, which is read to the host once for the
aggregation's sample times.

Exactness tiers (asserted by ``tests/test_hierarchy.py``):

* a single group reproduces the flat solve **bit-identically** (the outer
  level degenerates to "give the one group all ``n``" and the inner solve is
  the flat kernel on the same rows);
* multiple groups reproduce the flat **makespan** to within the solver
  tolerance wherever the aggregate is exact at the solution time (between
  sampled knots the aggregate interpolates, so allocations may shift a unit
  across a boundary — never increasing the makespan beyond the interpolation
  error);
* the numpy and jax backends, and ``shard_map``, return the same
  allocations (bit for bit on the CPU under x64).

Validation raises the same ``ValueError`` messages in the same order as the
flat paths, so the ``Scheduler`` facade can route policies without changing
its error surface.

Spans: ``hier.outer`` (with ``hier.aggregate``, the aggregation, and
``hier.outer.complete``, the integer shares) and ``hier.inner`` (with
``hier.inner.wait``, dispatch of the inner program until its result is on
the host); the counter ``hier.outer.leftover`` counts the units the outer
completion grants one by one.
"""

from __future__ import annotations

import heapq
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

from .modelbank import (
    GroupIndex,
    ModelBank,
    _monotone_rows,
    aggregate_allocs_host,
    aggregate_bank,
    aggregate_times,
    aggregate_width,
    group_index,
    sum_members,
)
from .partition import (
    _partition_continuous_bank,
    _partition_units_bank,
    _prep_unit_caps,
)

try:  # telemetry is optional: the solver runs identically without repro.obs
    from ..obs.telemetry import active as _obs_active
except ImportError:  # pragma: no cover - obs layer absent
    def _obs_active():
        return None

__all__ = ["Hierarchy"]


# Compiled shard_map'd inner solvers, keyed by (device count, completion
# routing, max_steps).  Module-level so rebuilding a Hierarchy (every
# observation fold changes the banks) never retraces: jax.jit's own cache
# handles shape changes, and the mesh is built once per device count
# (_SHARDING_CACHE).
_SHARD_FN_CACHE: dict = {}
_SHARDING_CACHE: dict = {}

# Inner-solve execution routing, by the size of ONE group's block: batched
# (one masked [g, ...] bisection) while a group's xs+ss block pair stays
# under this size, serial lax.map (each group's block through its whole
# bisection, one group after another) beyond it.  Measured on a CPU under
# x64: 4,864 groups of 5 batch in 29 ms against 166 ms serial, 100 x 1,000
# in 90 ms against 149 ms; 8 x 62,500 (6 MB a group) is even; 8 x 125,000
# (12 MB a group) runs serially in 1.04 s against 1.46 s batched.  Many small
# groups (a node-grouped cluster) therefore batch on every backend, and a few
# huge groups (the p=10^6 hierarchy) loop.  Bit-identical either way.
_HIER_SERIAL_MIN_GROUP_BYTES = 8 * 1024 * 1024

# Device aggregation materializes a [g, T, p_max, k-1] product intermediate
# (plus the [g, T, p_max] alloc cube copied back to host); route through it
# only while that stays modest.  Beyond the budget (e.g. p=10^6: several GB)
# the slab-wise host pass is the right tool.
_AGG_DEVICE_MAX_BYTES = 256 * 1024 * 1024


def _groups_sharding(ndev: int):
    """Sharding of a ``[g, ...]`` group-block array over the first ``ndev``
    devices, split along the group axis."""
    sharding = _SHARDING_CACHE.get(ndev)
    if sharding is None:
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:ndev]), ("groups",))
        sharding = _SHARDING_CACHE[ndev] = NamedSharding(mesh, P("groups"))
    return sharding


def _shard_inner_fn(ndev: int, completion_fast: bool, max_steps: int, serial: bool):
    key = (ndev, completion_fast, max_steps, serial)
    fn = _SHARD_FN_CACHE.get(key)
    if fn is None:
        from functools import partial

        import jax

        from .modelbank_jax import _hier_inner_map

        sharding = _groups_sharding(ndev)
        spec = sharding.spec
        body = partial(
            _hier_inner_map,
            rel_tol=1e-12,
            max_steps=max_steps,
            completion_fast=completion_fast,
            serial=serial,
        )
        # check_vma=False: the body is collective-free and every output is
        # fully sharded along "groups", so there is no varying-axis
        # information for JAX to check; skipping the check keeps the
        # while_loop bodies free of pvary annotations.
        fn = jax.jit(
            jax.shard_map(
                body,
                mesh=sharding.mesh,
                in_specs=(spec,) * 7,
                out_specs=(spec,) * 3,
                check_vma=False,
            )
        )
        _SHARD_FN_CACHE[key] = fn
    return fn


def complete_groups(
    gbank: ModelBank,
    shares: np.ndarray,
    gcaps: np.ndarray,
    rem: np.ndarray,
    leftover: int,
) -> np.ndarray:
    """Grant ``leftover`` units, one at a time, each to the group with the
    smallest ``(time(share + 1), -rem, index)`` that its cap admits.

    Where every group's candidate times ``time(share + j)`` are
    nondecreasing in ``j`` (the aggregates are monotone by construction),
    that order is a merge of sorted rows, and the granted units are the
    ``leftover`` smallest candidates: they are picked in bulk, ``J`` per
    group, doubling ``J`` while some group's ``J`` were all taken.  A row
    that is not sorted in floating point falls back to the unit-by-unit
    heap, which takes the same order exactly."""
    shares = np.array(shares, dtype=np.int64)
    if leftover <= 0:
        return shares
    g = shares.shape[0]
    J = min(leftover, 2)
    while True:
        cand = shares[:, None] + np.arange(1, J + 1)[None, :]
        ok = cand <= gcaps[:, None]
        t = np.stack([gbank.time(cand[:, j].astype(np.float64)) for j in range(J)], axis=1)
        if np.isnan(t[ok]).any() or np.any(ok[:, 1:] & (t[:, 1:] < t[:, :-1])):
            break
        gi, ji = np.nonzero(ok)
        if gi.size < leftover and J < leftover:
            J = min(2 * J, leftover)
            continue
        if gi.size < leftover:
            raise ValueError("caps infeasible during integer completion")
        pick = np.lexsort((ji, gi, -rem[gi], t[gi, ji]))[:leftover]
        take = np.bincount(gi[pick], minlength=g)
        if J < leftover and np.any((take == J) & (shares + J + 1 <= gcaps)):
            J = min(2 * J, leftover)
            continue
        return shares + take
    heap = [
        (gbank.time_one(i, float(shares[i] + 1)), -float(rem[i]), i)
        for i in range(g)
        if shares[i] + 1 <= gcaps[i]
    ]
    heapq.heapify(heap)
    while leftover > 0:
        if not heap:
            raise ValueError("caps infeasible during integer completion")
        _, negrem, i = heapq.heappop(heap)
        shares[i] += 1
        leftover -= 1
        if shares[i] + 1 <= gcaps[i]:
            heapq.heappush(heap, (gbank.time_one(i, float(shares[i] + 1)), negrem, i))
    return shares


class Hierarchy:
    """Two-level partitioner over a ``groups[p]`` assignment.

    Build with :meth:`from_bank` (a flat host bank), :meth:`from_device_bank`
    (a device carry: the inner blocks are gathered on the device) or
    :meth:`from_group_banks` (per-group banks, contiguous global indices;
    under ``shard_map`` no device ever holds the flat ``[p, k]`` bank).

    ``backend`` selects where the solves run (``"numpy"`` host solves,
    ``"jax"`` device programs); ``sharding="shard_map"`` (jax only)
    distributes the inner group blocks across devices.  Instances snapshot
    their banks at construction — rebuild after the underlying models change
    (an observation fold), which is cheap: the jit caches live on
    module-level functions, not on the instance.
    """

    def __init__(
        self,
        bank: ModelBank,
        index: GroupIndex,
        *,
        backend: str = "numpy",
        sharding: Optional[str] = None,
        max_group_knots: int = 64,
        dtype=None,
        device_bank=None,
        sub_banks: Optional[Sequence[ModelBank]] = None,
    ):
        if backend not in ("numpy", "jax"):
            raise ValueError(f"unknown hierarchy backend {backend!r}")
        if sharding not in (None, "shard_map"):
            raise ValueError(f"unknown sharding mode {sharding!r}")
        if sharding == "shard_map" and backend != "jax":
            raise ValueError('sharding="shard_map" requires backend="jax"')
        self.bank = bank
        self.index = index
        self.p = int(bank.p)
        self.backend = backend
        self.sharding = sharding
        self.max_group_knots = int(max_group_knots)
        self.dtype = dtype
        self.device_bank = device_bank  # JaxModelBank the blocks come from
        self._sub_banks = list(sub_banks) if sub_banks is not None else None
        self._blocks = None  # device [g, p_max, k] blocks, built lazily
        self._blocks_pad = None  # shard-padded variant, keyed by ndev
        self._agg_cache: dict = {}  # caps signature -> aggregated group bank
        # devices that held the last jax inner solve's result
        self.inner_devices: frozenset = frozenset()

    # -- construction --------------------------------------------------------

    @classmethod
    def from_bank(
        cls,
        bank: ModelBank,
        groups: Union[Sequence[int], GroupIndex],
        *,
        backend: str = "numpy",
        sharding: Optional[str] = None,
        max_group_knots: int = 64,
        dtype=None,
        device_bank=None,
    ) -> "Hierarchy":
        if isinstance(groups, GroupIndex):
            index = groups
            n_assigned = index.p
        else:
            garr = np.asarray(groups)
            if garr.ndim != 1 or garr.shape[0] != bank.p:
                raise ValueError(
                    f"groups must be a length-p assignment (got shape {garr.shape} "
                    f"for p={bank.p})"
                )
            index = group_index(garr)
            n_assigned = bank.p
        if n_assigned != bank.p:
            raise ValueError(
                f"groups must be a length-p assignment (got {n_assigned} for p={bank.p})"
            )
        return cls(
            bank,
            index,
            backend=backend,
            sharding=sharding,
            max_group_knots=max_group_knots,
            dtype=dtype,
            device_bank=device_bank,
        )

    @classmethod
    def from_device_bank(
        cls, jbank, groups: Union[Sequence[int], GroupIndex], **kw
    ) -> "Hierarchy":
        """Build on a device carry (``JaxModelBank``): one host read of the
        bank for the aggregation's sample times; the inner blocks are
        gathered from the carry on the device."""
        return cls.from_bank(jbank.to_bank(), groups, device_bank=jbank, **kw)

    @classmethod
    def from_group_banks(
        cls,
        banks: Sequence[ModelBank],
        *,
        backend: str = "numpy",
        sharding: Optional[str] = None,
        max_group_knots: int = 64,
        dtype=None,
    ) -> "Hierarchy":
        """Build from per-group banks; global processor indices run
        contiguously group by group.  The host joins them into one bank
        (padded to the widest); a device only ever receives group blocks —
        under ``shard_map`` its own ``ceil(g/ndev)`` of them."""
        banks = list(banks)
        k = max((int(b.xs.shape[1]) for b in banks), default=1)

        def widen(a: np.ndarray) -> np.ndarray:
            # width padding repeats the last column (masked by counts)
            return np.concatenate([a, np.repeat(a[:, -1:], k - a.shape[1], axis=1)], axis=1)

        flat = ModelBank(
            xs=np.concatenate([widen(b.xs) for b in banks]),
            ss=np.concatenate([widen(b.ss) for b in banks]),
            counts=np.concatenate([b.counts for b in banks]).astype(np.int64),
        )
        sizes = [b.p for b in banks]
        groups = np.repeat(np.arange(len(banks)), sizes)
        return cls(
            flat,
            group_index(groups),
            backend=backend,
            sharding=sharding,
            max_group_knots=max_group_knots,
            dtype=dtype,
            sub_banks=banks,
        )

    # -- shape ---------------------------------------------------------------

    @property
    def g(self) -> int:
        return self.index.g

    @property
    def members(self) -> List[np.ndarray]:
        return self.index.members

    @property
    def sub_banks(self) -> List[ModelBank]:
        """Per-group host banks ``[p_g, k]`` (the numpy inner solves)."""
        if self._sub_banks is None:
            # a monotone bank has only monotone rows; a non-monotone one
            # says nothing about THIS group's rows — resolve lazily
            mono = True if self.bank.monotone is True else None
            self._sub_banks = [
                ModelBank(
                    xs=self.bank.xs[idx], ss=self.bank.ss[idx],
                    counts=self.bank.counts[idx], monotone=mono,
                )
                for idx in self.members
            ]
        return self._sub_banks

    def _group_monotone(self) -> np.ndarray:
        """Per-group monotone-time flags (the inner completion routing)."""
        if self._sub_banks is not None:
            return np.array([b.is_monotone() for b in self._sub_banks], dtype=bool)
        if self.bank.monotone is True:
            return np.ones(self.g, dtype=bool)
        rows = _monotone_rows(self.bank.xs, self.bank.ss, self.bank.counts)
        return np.all(self.index.gather(rows, True), axis=1)

    def max_shard_elems(self) -> int:
        """Largest number of bank elements (xs plus ss knots) any single
        device holds for the inner solves — the memory gate of the p=10^6
        row.  On the jax backend it is read from where the group blocks
        actually live: under ``shard_map`` each device should hold only its
        ``ceil(g/ndev)`` blocks; otherwise one device holds all ``g``.  On
        the numpy backend the host holds all ``g``."""
        if self.backend != "jax":
            p_max = int(self.index.idx.shape[1])
            return 2 * self.g * p_max * int(self.bank.xs.shape[1])
        if self.sharding == "shard_map":
            import jax

            xs, ss, _, _ = self._padded_blocks(max(len(jax.devices()), 1))
        else:
            xs, ss, _ = self._ensure_blocks()
        per_device: dict = {}
        for arr in (xs, ss):
            for shard in arr.addressable_shards:
                size = int(np.prod(shard.data.shape))
                per_device[shard.device] = per_device.get(shard.device, 0) + size
        return max(per_device.values())

    # -- the two-level solve -------------------------------------------------

    def partition_units(
        self,
        n: int,
        caps: Optional[Sequence[int]] = None,
        *,
        min_units: int = 0,
        completion: str = "auto",
        rel_tol: float = 1e-12,
        max_steps: int = 200,
        with_t: bool = False,
    ):
        """Integer partition of ``n`` units over all ``p`` processors.

        Validation (messages and order) mirrors the flat paths exactly.
        Returns the ``[p]`` allocation list; with ``with_t=True`` returns
        ``(allocations, t_outer)`` where ``t_outer`` is the outer solve's
        equal-time point on the group aggregates.
        """
        if completion not in ("auto", "threshold", "greedy"):
            raise ValueError(f"unknown completion mode {completion!r}")
        n = int(n)
        if isinstance(caps, np.ndarray) and caps.dtype.kind in "iu":
            # vectorized mirror of _prep_unit_caps — the fleet hands the
            # per-job icaps array straight through every round, and a
            # per-element Python int() pass at p >= 10^4 would cost more
            # than the outer solve itself
            if n < 0:
                raise ValueError("n must be non-negative")
            if min_units * self.p > n:
                raise ValueError(
                    f"min_units={min_units} infeasible for n={n}, p={self.p}"
                )
            caps_arr = caps.astype(np.int64, copy=False)
            if min_units > 0:
                bad = caps_arr < min_units
                if bad.any():
                    i = int(np.argmax(bad))
                    raise ValueError(
                        f"min_units={min_units} infeasible: "
                        f"caps[{i}]={int(caps_arr[i])} < min_units"
                    )
        else:
            icaps = _prep_unit_caps(self.p, n, caps, min_units)
            caps_arr = np.asarray(icaps, dtype=np.int64)
        if self.p == 0:
            raise ValueError("no processors")
        if n == 0:
            out = [0] * self.p
            return (out, 0.0) if with_t else out
        clipped = np.minimum(caps_arr.astype(np.float64), float(n))
        if clipped.sum() < n:
            raise ValueError(f"infeasible: sum(caps)={clipped.sum()} < n={float(n)}")
        if np.any((caps_arr > 0) & (self.bank.counts == 0)):
            raise ValueError("empty FPM")

        tel = _obs_active()
        rec = tel is not None and tel.enabled
        if rec:
            tok = tel.begin("hier.outer", groups=self.g, n=n)
        shares, t_outer, _ = self._outer_shares(n, caps_arr, min_units)
        if rec:
            tel.end(tok)
            tok = tel.begin("hier.inner", groups=self.g, backend=self.backend)

        if self.backend == "jax":
            d_full = self._inner_jax(shares, caps_arr, min_units, completion, max_steps)
        else:
            d_full = np.zeros(self.p, dtype=np.int64)
            for sub, idx, ng in zip(self.sub_banks, self.members, shares):
                if len(idx) == 0:
                    continue
                d_sub, _ = _partition_units_bank(
                    sub,
                    int(ng),
                    [int(c) for c in caps_arr[idx]],
                    min_units=min_units,
                    completion=completion,
                )
                d_full[idx] = d_sub
        if rec:
            tel.end(tok)
        out = d_full.tolist()
        if sum(out) != n:
            raise AssertionError(f"allocations sum to {sum(out)}, not n={n}")
        return (out, float(t_outer)) if with_t else out

    def _outer_shares(
        self, n: int, caps_arr: np.ndarray, min_units: int
    ) -> Tuple[np.ndarray, float, ModelBank]:
        """Integer group shares summing to exactly ``n``: aggregate, bisect,
        floor, take back the min_units overshoot, then grant the boundary
        units between groups in ``(time(share+1), -frac_remainder, index)``
        order on the aggregate (:func:`complete_groups`)."""
        g = self.g
        gcaps_i = sum_members(self.index.gather(caps_arr), self.index.sizes)
        # Aggregation is the per-call tax of the two-level route; cache the
        # [g, k_g] bank on the instance.  When no member cap can bind (every
        # cap >= n, the caps=None fast path), the aggregate is computed
        # CAP-FREE so the one cached bank serves EVERY n — repeated
        # repartitions under drifting loads (the fleet serving loop) pay the
        # aggregation exactly once per fold.  Capped calls key on the exact
        # caps bytes.
        uncapped = bool(np.all(caps_arr >= n))
        key = "uncapped" if uncapped else caps_arr.tobytes()
        gbank = self._agg_cache.get(key)
        tel = _obs_active()
        rec = tel is not None and tel.enabled
        if rec:
            tel.counter(
                "hier.agg_cache.hit" if gbank is not None
                else "hier.agg_cache.miss"
            )
            tok = tel.begin("hier.aggregate")
        if gbank is None:
            caps_f = (
                np.full(self.p, np.inf)
                if uncapped
                else caps_arr.astype(np.float64)
            )
            gbank = self._aggregate(caps_f)
            if len(self._agg_cache) >= 8:
                self._agg_cache.clear()
            self._agg_cache[key] = gbank
        if rec:
            tel.end(tok)

        cont_caps = np.minimum(gcaps_i.astype(np.float64), float(n))
        if self.backend == "jax":
            xs_g, t_outer = self._outer_continuous_jax(gbank, n, cont_caps)
        else:
            xs_list, t_outer, _ = _partition_continuous_bank(
                gbank, float(n), list(cont_caps), rel_tol=1e-12, max_steps=200
            )
            xs_g = np.asarray(xs_list, dtype=np.float64)

        if rec:
            tok = tel.begin("hier.outer.complete")
        floors = min_units * self.index.sizes
        shares = np.maximum(floors, np.floor(xs_g).astype(np.int64))
        shares = np.minimum(shares, gcaps_i)
        leftover = int(n - shares.sum())

        if leftover < 0:
            # min_units floors overshot: take back from the groups whose
            # aggregate per-unit time is largest, round-robin (the flat
            # take-back, at group level).
            with np.errstate(invalid="ignore"):
                per_unit = gbank.time(shares.astype(np.float64)) / np.maximum(
                    shares, 1
                )
            order = sorted(range(g), key=lambda i: per_unit[i], reverse=True)
            k = 0
            while leftover < 0:
                i = order[k % g]
                if shares[i] > floors[i]:
                    shares[i] -= 1
                    leftover += 1
                k += 1

        if rec:
            tel.counter("hier.outer.leftover", leftover)
        rem = xs_g - np.floor(xs_g)
        shares = complete_groups(gbank, shares, gcaps_i, rem, leftover)
        if rec:
            tel.end(tok)
        if int(shares.sum()) != n:
            raise AssertionError(f"group shares sum to {int(shares.sum())}, not n={n}")
        return shares, float(t_outer), gbank

    def _outer_continuous_jax(
        self, gbank: ModelBank, n: int, caps: np.ndarray
    ) -> Tuple[np.ndarray, float]:
        """The continuous outer solve as the flat device program on the
        aggregate bank, padded to ``aggregate_width`` knots (one shape per
        group count, so one compile)."""
        import jax.numpy as jnp

        from .modelbank_jax import _partition_continuous_jit

        if np.any((caps > 0.0) & (gbank.counts == 0)):
            raise ValueError("empty FPM")
        width = max(aggregate_width(self.max_group_knots), int(gbank.xs.shape[1]))
        extra = width - int(gbank.xs.shape[1])
        xs = np.concatenate([gbank.xs, np.repeat(gbank.xs[:, -1:], extra, axis=1)], axis=1)
        ss = np.concatenate([gbank.ss, np.repeat(gbank.ss[:, -1:], extra, axis=1)], axis=1)
        alloc, t_star, _ = _partition_continuous_jit(
            jnp.asarray(xs, dtype=self.dtype),
            jnp.asarray(ss, dtype=self.dtype),
            jnp.asarray(gbank.counts),
            jnp.asarray(caps, dtype=self.dtype),
            jnp.asarray(n, dtype=self.dtype),
            jnp.asarray(1e-12, dtype=self.dtype),
            200,
        )
        return np.asarray(alloc, dtype=np.float64), float(t_star)

    def _aggregate(self, caps_f: np.ndarray) -> ModelBank:
        """The ``[g, k_g]`` aggregate bank (see ``aggregate_groups``); on the
        jax backend the member allocations are evaluated on the device."""
        ts, count = aggregate_times(self.bank, self.index, caps_f, self.max_group_knots)
        xsum = self._aggregate_allocs_device(caps_f, ts) if self.backend == "jax" else None
        if xsum is None:
            xsum = aggregate_allocs_host(self.bank, self.index, caps_f, ts)
        return aggregate_bank(ts, count, xsum)

    def _aggregate_allocs_device(
        self, caps_f: np.ndarray, ts: np.ndarray
    ) -> Optional[np.ndarray]:
        """Every group's summed member allocations at its sample times from
        one batched ``[g, T, p_max]`` device evaluation (two dispatches —
        ``_agg_products_jit`` + ``_agg_alloc_jit`` — split so LLVM's FMA
        contraction cannot re-round the two mul-feeding-subtract sites).
        The sample times are host-computed and the member sum is a host
        ``sum_members``, so the aggregate bank is bit-identical to the numpy
        backend's.  Returns None — the caller takes the slab-wise host pass
        — when the blocks are float32 (aggregation stays float64) or the
        device intermediates would exceed the budget."""
        g, width = ts.shape
        p_max = int(self.index.idx.shape[1])
        k = int(self.bank.xs.shape[1])
        # the [g, T, p, k-1] t*m product is the largest device intermediate
        if g * width * p_max * max(k - 1, 1) * 8 > _AGG_DEVICE_MAX_BYTES:
            return None
        xs_b, ss_b, counts_b = self._ensure_blocks()
        if xs_b.dtype != np.float64:
            return None
        import jax.numpy as jnp

        from .modelbank_jax import _agg_alloc

        out = np.asarray(
            _agg_alloc(
                xs_b, ss_b, counts_b,
                jnp.asarray(self.index.gather(caps_f)), jnp.asarray(ts),
            )
        )
        return sum_members(out, self.index.sizes)

    # -- jax inner solves ----------------------------------------------------

    def _host_blocks(self, pad: int = 0):
        """The ``[g + pad, p_max, k]`` group blocks as host arrays; the
        ``pad`` trailing lanes are inert (counts 0)."""
        xs = self.index.gather(self.bank.xs)
        ss = self.index.gather(self.bank.ss)
        counts = self.index.gather(self.bank.counts)
        if pad:
            xs = np.concatenate([xs, np.zeros((pad,) + xs.shape[1:])])
            ss = np.concatenate([ss, np.zeros((pad,) + ss.shape[1:])])
            counts = np.concatenate([counts, np.zeros((pad,) + counts.shape[1:], np.int64)])
        return xs, ss, counts

    def _ensure_blocks(self):
        if self._blocks is None:
            import jax.numpy as jnp

            if self.device_bank is not None:
                from .modelbank_jax import _gather_blocks_jit

                jb = self.device_bank
                xs, ss, counts = _gather_blocks_jit(
                    jb.xs, jb.ss, jb.counts,
                    jnp.asarray(self.index.idx), jnp.asarray(self.index.mask),
                )
                if self.dtype is not None and xs.dtype != np.dtype(self.dtype):
                    xs, ss = xs.astype(self.dtype), ss.astype(self.dtype)
                self._blocks = (xs, ss, counts)
            else:
                xs, ss, counts = self._host_blocks()
                self._blocks = (
                    jnp.asarray(xs, dtype=self.dtype),
                    jnp.asarray(ss, dtype=self.dtype),
                    jnp.asarray(counts),
                )
        return self._blocks

    def _padded_blocks(self, ndev: int):
        """Group blocks with ``g`` padded up to a multiple of ``ndev`` by
        inert zero lanes (counts 0 — their caps/shares are zeroed by the
        caller), so shard_map's even split always applies.  They are placed
        straight from the host onto their devices, ``ceil(g/ndev)`` blocks
        each: no device ever holds the whole bank."""
        pad = (-self.g) % ndev
        if self._blocks_pad is None or self._blocks_pad[0] != ndev:
            import jax

            sharding = _groups_sharding(ndev)
            xs, ss, counts = self._host_blocks(pad)
            self._blocks_pad = (
                ndev,
                jax.device_put(np.asarray(xs, dtype=self.dtype), sharding),
                jax.device_put(np.asarray(ss, dtype=self.dtype), sharding),
                jax.device_put(counts, sharding),
            )
        _, xs_p, ss_p, counts_p = self._blocks_pad
        return xs_p, ss_p, counts_p, pad

    @staticmethod
    def _inner_serial(xs, itemsize: int) -> bool:
        """Whether the inner program loops over groups (``lax.map``) rather
        than batching them: one group's block pair is past
        ``_HIER_SERIAL_MIN_GROUP_BYTES``."""
        group_bytes = 2 * int(xs.shape[1]) * int(xs.shape[2]) * itemsize
        return group_bytes > _HIER_SERIAL_MIN_GROUP_BYTES

    def _inner_jax(
        self,
        shares: np.ndarray,
        caps_arr: np.ndarray,
        min_units: int,
        completion: str,
        max_steps: int,
    ) -> np.ndarray:
        import jax.numpy as jnp

        from .modelbank_jax import _hier_inner_jit

        g = self.g
        caps_blk = self.index.gather(caps_arr)
        mu_blk = np.where(self.index.mask, min_units, 0).astype(np.int64)  # 0 pins padded rows
        if completion == "threshold":
            fast = np.ones(g, dtype=bool)
        elif completion == "greedy":
            fast = np.zeros(g, dtype=bool)
        else:
            # per-group auto routing: an adversarial non-monotone group
            # demotes only its own inner solve
            fast = self._group_monotone()
        cf = bool(fast.any())
        n_blk = np.asarray(shares, dtype=np.int64)

        itemsize = np.dtype(self.dtype).itemsize if self.dtype else 8
        tel = _obs_active()
        rec = tel is not None and tel.enabled
        if self.sharding == "shard_map":
            import jax

            ndev = max(len(jax.devices()), 1)
            xs, ss, counts, pad = self._padded_blocks(ndev)
            serial = self._inner_serial(xs, itemsize)
            if pad:
                zrow = np.zeros((pad, caps_blk.shape[1]), dtype=np.int64)
                caps_blk = np.concatenate([caps_blk, zrow])
                mu_blk = np.concatenate([mu_blk, zrow])
                n_blk = np.concatenate([n_blk, np.zeros(pad, dtype=np.int64)])
                fast = np.concatenate([fast, np.zeros(pad, dtype=bool)])
            fn = _shard_inner_fn(ndev, cf, max_steps, serial)
            if rec:
                tok = tel.begin("hier.inner.wait")
            # host operands: jit places each on its group shard directly
            d, ok, _t = fn(
                xs,
                ss,
                counts,
                caps_blk.astype(counts.dtype),
                n_blk,
                mu_blk.astype(counts.dtype),
                fast,
            )
            self.inner_devices = frozenset(d.sharding.device_set)
            d = np.asarray(d)[:g]
            ok = np.asarray(ok)[:g]
        else:
            xs, ss, counts = self._ensure_blocks()
            if rec:
                tok = tel.begin("hier.inner.wait")
            d, ok, _t = _hier_inner_jit(
                xs,
                ss,
                counts,
                jnp.asarray(caps_blk, counts.dtype),
                jnp.asarray(n_blk),
                jnp.asarray(mu_blk, counts.dtype),
                jnp.asarray(fast),
                rel_tol=1e-12,
                max_steps=max_steps,
                completion_fast=cf,
                serial=self._inner_serial(xs, itemsize),
            )
            self.inner_devices = frozenset(d.sharding.device_set)
            d = np.asarray(d)
            ok = np.asarray(ok)
        if rec:
            tel.end(tok)
        if not bool(np.all(ok)):
            raise ValueError("caps infeasible during integer completion")
        return self.index.scatter(d.astype(np.int64), self.p)
