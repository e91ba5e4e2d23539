"""One flat job under the program's ``Scheduler``: what every flat loop
shares.

A flat loop (``bench/loops/<name>.py``) subclasses :class:`FlatJob` and
defines ``setup`` and ``run`` from the pieces here: a session is built with
:meth:`FlatJob.construct`, tuned from cold with :meth:`FlatJob.autotune`,
and served with :meth:`FlatJob.observe`.  The benchmark's own vectorized
executor measures every round from the truth that the configuration names
(``bench/truth/<name>.py``), so no per-processor Python runs on the
measuring side.  Each session records what the program served (the
allocations it handed to the executor, the times measured for them, its
allocation after each round); :meth:`FlatJob.check` replays that record
through ``bench/reference.py`` once the window is closed.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from . import reference as ref
from .harness import Round, Session, Tracer, Window, load, now


def piecewise_time(xs: np.ndarray, ss: np.ndarray, d: np.ndarray) -> np.ndarray:
    """Time of ``d[i]`` units on processor ``i`` under the knot speeds
    ``xs/ss`` (linear interpolation, constant extension)."""
    p, k = xs.shape
    d = np.asarray(d, dtype=np.float64)
    rows = np.arange(p)
    j = np.clip(np.sum(xs <= d[:, None], axis=1) - 1, 0, k - 2)
    x0, x1 = xs[rows, j], xs[rows, j + 1]
    s0, s1 = ss[rows, j], ss[rows, j + 1]
    w = np.clip((d - x0) / (x1 - x0), 0.0, 1.0)
    return d / (s0 + w * (s1 - s0))


def guarantee_breaks(d, n: int, caps, min_units: int) -> int:
    """Sums other than ``n``, caps exceeded, allocations under ``min_units``."""
    d = np.asarray(d, dtype=np.int64)
    over = 0 if caps is None else int(np.sum(d > caps))
    return int(d.sum() != n) + over + int(np.sum(d < min_units))


class FlatExecutor:
    """The program's ``Executor``: the true times of a distribution on the
    session's cluster, times the round's factor."""

    def __init__(self, job: "FlatJob"):
        self.job = job
        self.num_procs = job.p
        self.xs = self.ss = None
        self.factor = 1.0
        self.t_out = None
        self.session: Optional[Session] = None

    def times(self, d):
        t0 = now()
        D = np.asarray(d, dtype=np.int64)
        T = piecewise_time(self.xs, self.ss, D) * self.factor
        self.job.window.gen_s += now() - t0
        return D, T

    def run(self, d):
        """One measured round inside ``autotune``."""
        t_in = now()
        if self.t_out is not None and self.job.in_window:
            self.job.window.rounds.append(Round(self.t_out, t_in, True))
        with self.job.tracer.annotate("bench.measure"):
            D, T = self.times(d)
            self.session.events.append(("measure", D, T))
            times = T.tolist()
        self.t_out = now()
        return times


class FlatJob:
    """A ``Scheduler`` over the configuration's cluster; see the module doc."""

    def __init__(self, config: dict, mix: dict, seed: int, *, control: bool = False,
                 tracer: Optional[Tracer] = None):
        from repro.core import Policy, Scheduler, SpeedStore

        self.Policy, self.Scheduler, self.SpeedStore = Policy, Scheduler, SpeedStore
        self.cfg, self.mix, self.seed = config, mix, int(seed)
        self.rng = np.random.default_rng(self.seed)
        truth = load("truth", config["truth"])
        self.clusters = [truth.build(config, int(s)) for s in config["truth_seeds"]]
        self.p = self.clusters[0][0].shape[0]
        if self.p != int(config["processors"]):
            raise ValueError(f"truth has {self.p} processors, configuration {config['processors']}")
        self.n = int(config["units"])
        self.caps = None if config.get("caps") is None else [int(config["caps"])] * self.p
        self.mu = int(config["min_units"])
        self.eps = float(config["eps"])
        self.smooth = float(config["smooth"])
        self.max_iter = int(config["max_iter"])
        self.dtype = np.float32 if control else None
        self.tracer = tracer or Tracer(False)
        self.window = Window()
        self.in_window = False
        self.ex = FlatExecutor(self)
        self.sched = None

    # -- pieces of a session --------------------------------------------------

    def construct(self) -> Session:
        t0 = now()
        with self.tracer.annotate("bench.construct"):
            store = self.SpeedStore.empty(self.p, backend="jax", dtype=self.dtype)
            self.sched = self.Scheduler(
                store, policy=self.Policy.DFPA, eps=self.eps, min_units=self.mu,
                caps=self.caps, smooth=self.smooth,
            )
        sess = Session(construct=(t0, now()))
        self.ex.session = sess
        self.ex.t_out = None
        if self.in_window:
            self.window.sessions.append(sess)
        return sess

    def autotune(self, sess: Session, cluster: int) -> None:
        """DFPA from an even split to ``eps`` on cluster ``cluster``."""
        self.ex.xs, self.ex.ss = self.clusters[cluster][:2]
        self.ex.factor = 1.0
        with self.tracer.annotate("bench.autotune"):
            res = self.sched.autotune(
                self.ex, self.n, self.eps, max_iter=self.max_iter, min_units=self.mu
            )
        t1 = now()
        if self.in_window:  # the converged round keeps its allocation
            self.window.rounds.append(Round(self.ex.t_out, t1, False))
        sess.tuned = (sess.construct[0], t1)
        sess.converged = bool(res.converged)
        sess.events.append(("tuned", list(self.sched.d), bool(res.converged)))

    def observe(self, sess: Session, factor: float = 1.0) -> None:
        """One served round: the times of the current allocation in, the
        next allocation out."""
        with self.tracer.annotate("bench.round"):
            with self.tracer.annotate("bench.measure"):
                self.ex.factor = factor
                D, T = self.ex.times(self.sched.d)
                times = T.tolist()
            t0 = now()
            with self.tracer.annotate("bench.observe"):
                changed = self.sched.observe(times)
            t1 = now()
        sess.events.append(("serve", D, T, self.sched.d))
        if self.in_window:
            self.window.rounds.append(Round(t0, t1, bool(changed)))

    def keep_carry(self, sess: Session) -> None:
        """The session's device bank, read through the public accessor."""
        sess.carry = self.sched.store.device_bank(snapshot=False)

    # -- the window's edges -----------------------------------------------------

    def ready(self) -> None:
        """End of set-up: every program has run, and the record is cleared."""
        self.sched.store.device_bank(snapshot=False).xs.block_until_ready()
        self.window = Window()

    def open(self) -> Window:
        self.in_window = True
        self.window.t0 = now()
        return self.window

    def close(self) -> Window:
        self.sched.store.device_bank(snapshot=False).xs.block_until_ready()
        self.window.t1 = now()
        self.tracer.stop()
        self.in_window = False
        return self.window

    def release(self) -> None:
        """Drop the program's live state (the recorded carries stay)."""
        self.sched = None

    # -- the comparison ---------------------------------------------------------

    def check(self, rng: np.random.Generator) -> Dict[str, float]:
        """Replay a sample of the window's sessions (the last always in it)
        through the reference; count guarantee breaks over every round."""
        sessions = [s for s in self.window.sessions if s.events]
        n_sample = min(int(self.mix["check_sessions"]), len(sessions))
        picks = set(rng.choice(len(sessions), size=n_sample, replace=False).tolist())
        picks.add(len(sessions) - 1)
        alloc, est_gap = 0, 0.0
        for k in sorted(picks):
            a, g = self._check_session(sessions[k], rng)
            alloc, est_gap = max(alloc, a), max(est_gap, g)
        caps = None if self.caps is None else np.asarray(self.caps)
        breaks = 0
        for s in self.window.sessions:
            for ev in s.events[s.window_from:]:
                if ev[0] in ("measure", "serve"):
                    breaks += guarantee_breaks(ev[1], self.n, caps, self.mu)
                if ev[0] == "serve":
                    breaks += guarantee_breaks(ev[3], self.n, caps, self.mu)
        return {"alloc_units": alloc, "estimate_rel": est_gap, "guarantee_breaks": breaks}

    def _check_session(self, sess: Session, rng: np.random.Generator):
        """``(alloc_units, estimate_rel)`` of one session.  Every measured
        round, and every served round that keeps its allocation, is
        compared with the reference; served rounds that repartition are
        compared on a sample of ``check_rounds`` drawn from the seed, the
        last always in it (each costs a full solve)."""
        caps = np.full(self.p, self.n, dtype=np.int64) if self.caps is None \
            else np.asarray(self.caps, dtype=np.int64)
        rs = ref.FlatSession(self.p, self.smooth)
        events = sess.events
        served = [i for i, ev in enumerate(events)
                  if ev[0] == "serve" and i >= sess.window_from]
        n_rounds = min(int(self.mix.get("check_rounds", len(served))), len(served))
        due = set(rng.choice(served, size=n_rounds, replace=False).tolist()) if served else set()
        if served:
            due.add(served[-1])
        alloc_off = 0
        seen: Dict[tuple, list] = {}
        expect = None  # the reference's next measured allocation, when it has one
        measuring, it = True, 0

        def gap(d_prog, d_ref) -> int:
            return int(np.max(np.abs(np.asarray(d_prog, np.int64) - np.asarray(d_ref, np.int64))))

        for i_ev, ev in enumerate(events):
            timed = i_ev >= sess.window_from  # comparisons count from the window on
            if ev[0] == "measure":
                _, D, T = ev
                if not measuring:
                    seen, it, measuring = {}, 0, True
                if expect is None and it == 0:
                    base, rem = divmod(self.n, self.p)
                    expect = [base + (1 if i < rem else 0) for i in range(self.p)]
                if expect is not None and timed:
                    alloc_off = max(alloc_off, gap(D, expect))
                rs.fold_measured(D.tolist(), T.tolist())
                seen[tuple(D.tolist())] = T.tolist()
                it += 1
                expect = None
                if ref.imbalance(T.tolist()) <= self.eps or it >= self.max_iter:
                    continue
                d_new, _ = ref.partition(rs.est, self.n, caps, self.mu)
                d_new = [int(v) for v in d_new]
                if tuple(d_new) in seen:
                    d_new = ref.probe_neighbour(d_new, seen[tuple(d_new)], seen, self.caps, self.mu)
                expect = d_new
            elif ev[0] == "tuned":
                measuring, expect = False, None
            else:
                _, D, T, d_out = ev
                rs.fold_served(D.tolist(), T.tolist())
                if not timed:
                    continue
                if ref.imbalance(T.tolist()) <= self.eps:  # the allocation stays
                    alloc_off = max(alloc_off, gap(d_out, D))
                elif i_ev in due:
                    d_next, _ = ref.partition(rs.est, self.n, caps, self.mu)
                    alloc_off = max(alloc_off, gap(d_out, d_next))
        carry = sess.carry
        est_gap = ref.estimate_gap(rs.est, np.asarray(carry.xs), np.asarray(carry.ss),
                                   np.asarray(carry.counts))
        return alloc_off, est_gap
