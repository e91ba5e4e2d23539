"""``steady``: a converged job serving rounds whose relative speeds hold.

Set-up tunes cluster ``cluster`` once and serves ``warm_rounds`` rounds.
Every window round then hands ``observe`` the true times of the current
allocation, all scaled by the round's factor: the measured time of one
block of work on a real host, over the median of its series
(``bench/traffic/<factors>``, made by ``bench/measure_noise.py``).  A common
factor leaves every machine's share as it was, so no round repartitions,
while every fold carries new speeds.  The benchmark seed orders the series;
round ``r`` takes entry ``r`` modulo its length.
"""

from __future__ import annotations

import numpy as np

from bench.flat import FlatJob
from bench.harness import BENCH, Window, now


def read_factors(name: str) -> np.ndarray:
    """The measured series, as factors over its median."""
    seconds = np.loadtxt(BENCH / "traffic" / name, comments="#", ndmin=1)
    return seconds / np.median(seconds)


class Loop(FlatJob):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.factors = self.rng.permutation(read_factors(self.mix["factors"]))

    def factor(self, r: int) -> float:
        return float(self.factors[r % len(self.factors)])

    def setup(self) -> None:
        self.session = self.construct()
        self.autotune(self.session, int(self.mix["cluster"]))
        for r in range(int(self.mix["warm_rounds"])):
            self.observe(self.session, self.factor(r))
        self.ready()

    def run(self, seconds: float) -> Window:
        sess = self.session
        w = self.open()
        sess.window_from = len(sess.events)
        w.sessions.append(sess)
        deadline = w.t0 + seconds
        r = int(self.mix["warm_rounds"])
        while now() < deadline:
            self.tracer.tick(now() - w.t0)
            self.observe(sess, self.factor(r))
            r += 1
        self.keep_carry(sess)
        return self.close()
