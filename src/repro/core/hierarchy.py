"""Two-level hierarchical partitioning — outer solve over group aggregates,
inner per-group solves on each group's own sub-bank.

The flat partitioner is one ``O(p k)`` pass per bisection step; at p=10^4 the
stacked ``[q, p, k]`` working set falls out of CPU cache and the stacked
measurement round loses to sequential (``BENCH_fleet.json``).  The paper's
platforms are *hierarchically* heterogeneous — hosts grouped by class, groups
behind a shared interconnect — and the natural fix is the paper's own
structure:

1. **Aggregate** each group behind a composite performance model
   (``aggregate_groups`` in ``modelbank.py``): the exact
   sum-of-allocs-at-equal-time composition sampled at the union of member
   knots, a ``[g, k_g]`` bank that is monotone-time by construction.
2. **Outer solve**: the ordinary ``t*`` bisection on the group bank —
   ``O(g k_g)`` per step — then floor + take-back + the existing greedy
   tie-break over groups, so the integer group shares sum to exactly ``n``.
3. **Inner solves**: each group's share is partitioned over its members on
   the group's ``[p_g, k]`` sub-bank.  On the numpy backend this is the
   ordinary host solve per group; on the jax backend all groups run in ONE
   device program (``lax.map`` over ``[g, p_max, k]`` blocks — sequential per
   group, so each block stays cache-resident through its whole bisection);
   under ``sharding="shard_map"`` the same body runs per device over its
   local group lanes, so no single device ever materializes more than
   ``ceil(g/ndev)`` blocks of the bank (``max_shard_elems``).

Exactness tiers (asserted by ``tests/test_hierarchy.py``):

* a single group reproduces the flat solve **bit-identically** (the outer
  level degenerates to "give the one group all ``n``" and the inner solve is
  the flat kernel on the same rows);
* multiple groups reproduce the flat **makespan** to within the solver
  tolerance wherever the aggregate is exact at the solution time (between
  sampled knots the aggregate interpolates, so allocations may shift a unit
  across a boundary — never increasing the makespan beyond the interpolation
  error).

Validation raises the same ``ValueError`` messages in the same order as the
flat paths, so the ``Scheduler`` facade can route policies without changing
its error surface.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from .modelbank import (
    ModelBank,
    _aggregate_one,
    _aggregate_times,
    _points_from_samples,
    group_members,
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

# Inner-solve execution routing: batched (one masked [g, ...] bisection)
# while the xs+ss block set a device touches fits comfortably in L2-ish
# cache, serial lax.map (each group's block cache-resident through its
# whole bisection) beyond that.  Bit-identical either way.
_HIER_BATCH_MAX_BYTES = 2 * 1024 * 1024

# Device aggregation materializes a [g, T, p_max, k-1] product intermediate
# (plus the [g, T, p_max] alloc cube copied back to host); route through it
# only while that stays modest.  Beyond the budget (e.g. p=10^6: several GB)
# the chunked host pass is the right tool — aggregation there runs once per
# fold and the uncapped cache serves the steady state.
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


class Hierarchy:
    """Two-level partitioner over a ``groups[p]`` assignment.

    Build with :meth:`from_bank` (slices an existing flat bank into per-group
    sub-banks) or :meth:`from_group_banks` (the p=10^6 path: the flat
    ``[p, k]`` bank is NEVER materialized — callers hand over per-group banks
    directly and global processor indices are assigned contiguously).

    ``backend`` selects the inner solver (``"numpy"`` host loops per group,
    ``"jax"`` one ``lax.map`` device program over group blocks); ``sharding=
    "shard_map"`` (jax only) distributes the group blocks across devices.
    Instances snapshot their banks at construction — rebuild after the
    underlying models change (an observation fold), which is cheap: the jit
    caches live on module-level functions, not on the instance.
    """

    def __init__(
        self,
        sub_banks: Sequence[ModelBank],
        members: Sequence[np.ndarray],
        p: int,
        *,
        backend: str = "numpy",
        sharding: Optional[str] = None,
        max_group_knots: int = 64,
        dtype=None,
    ):
        if backend not in ("numpy", "jax"):
            raise ValueError(f"unknown hierarchy backend {backend!r}")
        if sharding not in (None, "shard_map"):
            raise ValueError(f"unknown sharding mode {sharding!r}")
        if sharding == "shard_map" and backend != "jax":
            raise ValueError('sharding="shard_map" requires backend="jax"')
        self.sub_banks = list(sub_banks)
        self.members = [np.asarray(m, dtype=np.int64) for m in members]
        self.p = int(p)
        self.backend = backend
        self.sharding = sharding
        self.max_group_knots = int(max_group_knots)
        self.dtype = dtype
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
        groups: Sequence[int],
        *,
        backend: str = "numpy",
        sharding: Optional[str] = None,
        max_group_knots: int = 64,
        dtype=None,
    ) -> "Hierarchy":
        garr = np.asarray(groups)
        if garr.ndim != 1 or garr.shape[0] != bank.p:
            raise ValueError(
                f"groups must be a length-p assignment (got shape {garr.shape} "
                f"for p={bank.p})"
            )
        _, members = group_members(groups)
        subs = [
            ModelBank(
                xs=bank.xs[idx],
                ss=bank.ss[idx],
                counts=bank.counts[idx],
                # a monotone bank has only monotone rows; a non-monotone one
                # says nothing about THIS group's rows — resolve lazily
                monotone=True if bank.monotone is True else None,
            )
            for idx in members
        ]
        return cls(
            subs,
            members,
            bank.p,
            backend=backend,
            sharding=sharding,
            max_group_knots=max_group_knots,
            dtype=dtype,
        )

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
        """Build from per-group banks without ever materializing the flat
        ``[p, k]`` bank — the memory story at p=10^6, where a single flat
        float64 bank would not even allocate comfortably.  Global processor
        indices run contiguously group by group."""
        banks = list(banks)
        members: List[np.ndarray] = []
        off = 0
        for b in banks:
            members.append(np.arange(off, off + b.p, dtype=np.int64))
            off += b.p
        return cls(
            banks,
            members,
            off,
            backend=backend,
            sharding=sharding,
            max_group_knots=max_group_knots,
            dtype=dtype,
        )

    # -- shape ---------------------------------------------------------------

    @property
    def g(self) -> int:
        return len(self.sub_banks)

    def max_shard_elems(self) -> int:
        """Largest number of bank elements (xs plus ss knots) any single
        device holds for the inner solves — the memory gate of the p=10^6
        row.  On the jax backend it is read from where the group blocks
        actually live: under ``shard_map`` each device should hold only its
        ``ceil(g/ndev)`` blocks; otherwise one device holds all ``g``.  On
        the numpy backend the host holds all ``g``."""
        if self.backend != "jax":
            p_max = max((b.p for b in self.sub_banks), default=1) or 1
            k = max((int(b.xs.shape[1]) for b in self.sub_banks), default=1)
            return 2 * self.g * p_max * k
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
        for sub, idx in zip(self.sub_banks, self.members):
            if np.any((caps_arr[idx] > 0) & (sub.counts == 0)):
                raise ValueError("empty FPM")

        tel = _obs_active()
        rec = tel is not None and tel.enabled
        if rec:
            tok = tel.begin("hier.outer", groups=self.g, n=n)
        shares, t_outer, gbank = self._outer_shares(n, caps_arr, min_units)
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
        out = [int(v) for v in d_full]
        assert sum(out) == n
        return (out, float(t_outer)) if with_t else out

    def _outer_shares(
        self, n: int, caps_arr: np.ndarray, min_units: int
    ) -> Tuple[np.ndarray, float, ModelBank]:
        """Integer group shares summing to exactly ``n``: aggregate, bisect,
        floor, take back the min_units overshoot, then grant the boundary
        units between groups by the existing greedy tie-break
        ``(time(share+1), -frac_remainder, index)`` on the aggregate."""
        g = self.g
        gcaps_i = np.array(
            [caps_arr[idx].sum() for idx in self.members], dtype=np.int64
        )
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
        if tel is not None and tel.enabled:
            tel.counter(
                "hier.agg_cache.hit" if gbank is not None
                else "hier.agg_cache.miss"
            )
        if gbank is None:
            caps_f = (
                np.full(self.p, np.inf)
                if uncapped
                else caps_arr.astype(np.float64)
            )
            gbank = ModelBank.from_point_lists(self._aggregate_pts(caps_f))
            gbank.monotone = True  # by construction: knots at sorted times
            if len(self._agg_cache) >= 8:
                self._agg_cache.clear()
            self._agg_cache[key] = gbank

        floors = np.array(
            [min_units * len(idx) for idx in self.members], dtype=np.int64
        )
        xs_list, t_outer, _ = _partition_continuous_bank(
            gbank,
            float(n),
            [min(float(c), float(n)) for c in gcaps_i],
            rel_tol=1e-12,
            max_steps=200,
        )
        xs_g = np.asarray(xs_list, dtype=np.float64)
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

        rem = xs_g - np.floor(xs_g)
        for _ in range(leftover):
            best_i, best_key = -1, None
            for i in range(g):
                if shares[i] + 1 > gcaps_i[i]:
                    continue
                key = (gbank.time_one(i, float(shares[i] + 1)), -float(rem[i]))
                if best_key is None or key < best_key:
                    best_i, best_key = i, key
            if best_i < 0:
                raise ValueError("caps infeasible during integer completion")
            shares[best_i] += 1
        assert int(shares.sum()) == n
        return shares, float(t_outer), gbank

    def _aggregate_pts(self, caps_f: np.ndarray) -> List[Tuple[List[float], List[float]]]:
        """Per-group aggregate knot lists, device-evaluated when cheap."""
        if self.backend == "jax":
            pts = self._aggregate_pts_device(caps_f)
            if pts is not None:
                return pts
        return [
            _aggregate_one(sub, caps_f[idx], self.max_group_knots)
            for sub, idx in zip(self.sub_banks, self.members)
        ]

    def _aggregate_pts_device(
        self, caps_f: np.ndarray
    ) -> Optional[List[Tuple[List[float], List[float]]]]:
        """Evaluate every group's member allocations in one batched
        ``[g, T, p_max]`` device program instead of g chunked numpy passes.

        The host pass materializes ~a dozen ``[T, p, k-1]`` temporaries per
        group and is memory-bandwidth bound; in the fleet steady state every
        fold widens ``k``, so by round 8 aggregation dominates the two-level
        repartition.  XLA fuses the same expression into one sweep (two
        dispatches — ``_agg_products_jit`` + ``_agg_alloc_jit`` — split so
        LLVM's FMA contraction cannot re-round the two mul-feeding-subtract
        sites).  The sample-time grid stays host-computed and the per-group
        member sum stays a host ``np.sum`` over the same axis order, so the
        aggregate bank is bit-identical to the numpy backend's.  Returns
        None — caller falls back to the chunked host loop — when blocks are
        float32 (aggregation stays float64) or the device intermediates
        would be large: at p=10^6 they reach GBs, and the once-per-fold host
        pass with 1 MB chunks is the right tool there.
        """
        ts_list = [
            _aggregate_times(sub, caps_f[idx], self.max_group_knots)
            for sub, idx in zip(self.sub_banks, self.members)
        ]
        t_max = max((int(t.size) for t in ts_list), default=0)
        if t_max == 0:
            return [([], []) for _ in ts_list]
        xs_b, ss_b, counts_b = self._ensure_blocks()
        if xs_b.dtype != np.float64:
            return None
        p_max = int(xs_b.shape[1])
        k_b = int(xs_b.shape[2])
        # the [g, T, p, k-1] t*m product is the largest device intermediate
        if self.g * t_max * p_max * max(k_b - 1, 1) * 8 > _AGG_DEVICE_MAX_BYTES:
            return None
        import jax.numpy as jnp

        from .modelbank_jax import _agg_alloc

        ts_pad = np.ones((self.g, t_max), dtype=np.float64)
        caps_pad = np.zeros((self.g, p_max), dtype=np.float64)
        for gi, (t, idx) in enumerate(zip(ts_list, self.members)):
            if t.size:
                # pad by repeating the last sample: evaluated, then sliced
                # away before the member sum
                ts_pad[gi, : t.size] = t
                ts_pad[gi, t.size :] = t[-1]
            caps_pad[gi, : len(idx)] = caps_f[idx]
        out = np.asarray(
            _agg_alloc(
                xs_b, ss_b, counts_b, jnp.asarray(caps_pad), jnp.asarray(ts_pad)
            )
        )
        pts: List[Tuple[List[float], List[float]]] = []
        for gi, (t, idx) in enumerate(zip(ts_list, self.members)):
            if t.size == 0:
                pts.append(([], []))
                continue
            xs_g = out[gi, : t.size, : len(idx)].sum(axis=1)
            pts.append(_points_from_samples(t, xs_g))
        return pts

    # -- jax inner solves ----------------------------------------------------

    def _host_blocks(self, pad: int = 0):
        """The ``[g + pad, p_max, k]`` group blocks as host arrays; the
        ``pad`` trailing lanes are inert (counts 0)."""
        g = self.g
        p_max = max((b.p for b in self.sub_banks), default=0) or 1
        k = max((int(b.xs.shape[1]) for b in self.sub_banks), default=1)
        xs = np.zeros((g + pad, p_max, k), dtype=np.float64)
        ss = np.zeros_like(xs)
        counts = np.zeros((g + pad, p_max), dtype=np.int64)
        for gi, b in enumerate(self.sub_banks):
            pg, kb = b.xs.shape
            if pg == 0:
                continue
            xs[gi, :pg, :kb] = b.xs
            ss[gi, :pg, :kb] = b.ss
            if kb < k:
                # width padding repeats the last column, the
                # from_point_lists convention (masked by counts anyway)
                xs[gi, :pg, kb:] = b.xs[:, -1:]
                ss[gi, :pg, kb:] = b.ss[:, -1:]
            counts[gi, :pg] = b.counts
        return xs, ss, counts

    def _ensure_blocks(self):
        if self._blocks is None:
            import jax.numpy as jnp

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
        p_max = max((b.p for b in self.sub_banks), default=0) or 1
        caps_blk = np.zeros((g, p_max), dtype=np.int64)
        mu_blk = np.zeros((g, p_max), dtype=np.int64)  # 0 pins padded rows
        for gi, idx in enumerate(self.members):
            caps_blk[gi, : len(idx)] = caps_arr[idx]
            mu_blk[gi, : len(idx)] = min_units
        if completion == "threshold":
            fast = np.ones(g, dtype=bool)
        elif completion == "greedy":
            fast = np.zeros(g, dtype=bool)
        else:
            # per-group auto routing: an adversarial non-monotone group
            # demotes only its own inner solve (host flags, cached per sub)
            fast = np.array([b.is_monotone() for b in self.sub_banks], dtype=bool)
        cf = bool(fast.any())
        n_blk = np.asarray(shares, dtype=np.int64)

        itemsize = np.dtype(self.dtype).itemsize if self.dtype else 8
        if self.sharding == "shard_map":
            import jax

            ndev = max(len(jax.devices()), 1)
            xs, ss, counts, pad = self._padded_blocks(ndev)
            # route by the block bytes a single DEVICE touches
            local_bytes = 2 * int(xs.size) * itemsize // ndev
            serial = local_bytes > _HIER_BATCH_MAX_BYTES
            if pad:
                zrow = np.zeros((pad, p_max), dtype=np.int64)
                caps_blk = np.concatenate([caps_blk, zrow])
                mu_blk = np.concatenate([mu_blk, zrow])
                n_blk = np.concatenate([n_blk, np.zeros(pad, dtype=np.int64)])
                fast = np.concatenate([fast, np.zeros(pad, dtype=bool)])
            fn = _shard_inner_fn(ndev, cf, max_steps, serial)
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
                serial=2 * int(xs.size) * itemsize > _HIER_BATCH_MAX_BYTES,
            )
            self.inner_devices = frozenset(d.sharding.device_set)
            d = np.asarray(d)
            ok = np.asarray(ok)
        if not bool(np.all(ok)):
            raise ValueError("caps infeasible during integer completion")
        d_full = np.zeros(self.p, dtype=np.int64)
        for gi, idx in enumerate(self.members):
            d_full[idx] = d[gi, : len(idx)]
        return d_full
