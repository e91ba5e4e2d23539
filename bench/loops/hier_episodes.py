"""``hier-episodes``: repeated cold starts of one job over a grouped
platform, through the program's two-level route.

Every episode builds a fresh ``Scheduler(groups=<node of each processor>)``
on a jax store and autotunes from an even split until the slowest processor
takes at most ``eps`` longer than the fastest; every repartition of the
session runs through ``Hierarchy`` (aggregation, outer solve, inner solves).
Episode ``e`` runs truth draw ``order[e % 3]``; the benchmark seed draws
``order``.  Set-up tunes each draw once, which compiles every shape the
window reaches.  A program whose autotune does not take the two-level route
fails the run at its first episode.

The measurement after each repartition runs under a ``bench.round``
annotation, so the traced stretch's device trace counts repartitions.  The
comparison replays sampled sessions (the last always among them) through
``bench/hier_reference.py``: every measured allocation against the
reference's two-level partition of the same estimates, and its makespan
against the reference's flat optimum.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from bench import hier_reference as href
from bench import reference as ref
from bench.flat import FlatExecutor, FlatJob, guarantee_breaks
from bench.harness import Round, Session, Window, now


class HierExecutor(FlatExecutor):
    def run(self, d):
        if self.t_out is None:  # a session's first measurement follows no repartition
            return super().run(d)
        with self.job.tracer.annotate("bench.round"):
            return super().run(d)


class Loop(FlatJob):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.ex = HierExecutor(self)
        self.order = self.rng.permutation(len(self.clusters))
        self.max_knots = int(self.cfg["max_group_knots"])
        self.members = [href.members_of(c[2]) for c in self.clusters]
        self.cluster = 0

    def construct(self) -> Session:
        t0 = now()
        with self.tracer.annotate("bench.construct"):
            store = self.SpeedStore.empty(self.p, backend="jax", dtype=self.dtype)
            self.sched = self.Scheduler(
                store, policy=self.Policy.DFPA, eps=self.eps, min_units=self.mu,
                caps=self.caps, smooth=self.smooth,
                groups=self.clusters[self.cluster][2], max_group_knots=self.max_knots,
            )
        sess = Session(construct=(t0, now()))
        sess.cluster = self.cluster
        sess.outer_shape = (len(self.members[self.cluster]), self.max_knots)
        self.ex.session = sess
        self.ex.t_out = None
        if self.in_window:
            self.window.sessions.append(sess)
        return sess

    def autotune(self, sess: Session, cluster: int) -> None:
        self.ex.xs, self.ex.ss = self.clusters[cluster][:2]
        self.ex.factor = 1.0
        with self.tracer.annotate("bench.autotune"):
            res = self.sched.autotune(
                self.ex, self.n, self.eps, max_iter=self.max_iter, min_units=self.mu
            )
        t1 = now()
        if res.diagnostics.get("route") != "hier":
            raise RuntimeError("the program's autotune did not take the two-level route")
        if self.in_window:  # the converged round keeps its allocation
            self.window.rounds.append(Round(self.ex.t_out, t1, False))
        sess.tuned = (sess.construct[0], t1)
        sess.converged = bool(res.converged)
        sess.events.append(("tuned", list(self.sched.d), bool(res.converged)))

    def episode(self, e: int) -> None:
        self.cluster = int(self.order[e % len(self.order)])
        sess = self.construct()
        self.autotune(sess, self.cluster)
        self.keep_carry(sess)

    def setup(self) -> None:
        for e in range(len(self.order)):
            self.episode(e)
        self.ready()

    def run(self, seconds: float) -> Window:
        w = self.open()
        deadline = w.t0 + seconds
        e = 0
        while now() < deadline:
            self.tracer.tick(now() - w.t0)
            with self.tracer.annotate("bench.episode"):
                self.episode(e)
            e += 1
        return self.close()

    # -- the comparison ---------------------------------------------------------

    def check(self, rng: np.random.Generator) -> Dict[str, float]:
        sessions = [s for s in self.window.sessions if s.events]
        n_sample = min(int(self.mix["check_sessions"]), len(sessions))
        picks = set(rng.choice(len(sessions), size=n_sample, replace=False).tolist())
        picks.add(len(sessions) - 1)
        alloc, est_gap, span = 0, 0.0, 0.0
        for k in sorted(picks):
            a, g, m = self._check_session(sessions[k])
            alloc, est_gap, span = max(alloc, a), max(est_gap, g), max(span, m)
        caps = None if self.caps is None else np.asarray(self.caps)
        breaks = sum(guarantee_breaks(ev[1], self.n, caps, self.mu)
                     for s in self.window.sessions for ev in s.events if ev[0] == "measure")
        return {"alloc_units": alloc, "estimate_rel": est_gap, "guarantee_breaks": breaks,
                "makespan_rel": span}

    def _check_session(self, sess: Session):
        """``(alloc_units, estimate_rel, makespan_rel)`` of one session: every
        measured allocation against the reference's (the even split first,
        then the two-level partition of the estimates so far, or the probe
        the measuring loop takes where that partition was measured already)."""
        caps = np.full(self.p, self.n, dtype=np.int64) if self.caps is None \
            else np.asarray(self.caps, dtype=np.int64)
        members = self.members[sess.cluster]
        rs = ref.FlatSession(self.p, self.smooth)
        seen: Dict[tuple, list] = {}
        base, rem = divmod(self.n, self.p)
        expect = [base + (1 if i < rem else 0) for i in range(self.p)]
        partitioned, it = False, 0
        alloc_off, span = 0, 0.0
        for ev in sess.events:
            if ev[0] != "measure":
                continue
            _, D, T = ev
            if expect is not None:
                alloc_off = max(alloc_off, int(np.max(np.abs(D - np.asarray(expect, np.int64)))))
            if partitioned:  # the served two-level allocation against the flat optimum
                d_flat, _ = ref.partition(rs.est, self.n, caps, self.mu)
                span = max(span, href.makespan(rs.est, D) / href.makespan(rs.est, d_flat) - 1.0)
            rs.fold_measured(D.tolist(), T.tolist())
            seen[tuple(D.tolist())] = T.tolist()
            it += 1
            expect, partitioned = None, False
            if ref.imbalance(T.tolist()) <= self.eps or it >= self.max_iter:
                continue
            d_new, _ = href.partition(rs.est, members, self.n, caps, self.mu, self.max_knots)
            d_new = [int(v) for v in d_new]
            partitioned = tuple(d_new) not in seen
            if not partitioned:
                d_new = ref.probe_neighbour(d_new, seen[tuple(d_new)], seen, self.caps, self.mu)
            expect = d_new
        carry = sess.carry
        est_gap = ref.estimate_gap(rs.est, np.asarray(carry.xs), np.asarray(carry.ss),
                                   np.asarray(carry.counts))
        return alloc_off, est_gap, span
