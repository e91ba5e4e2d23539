"""``episodes``: repeated self-adaptable runs, the paper's cold start.

Every episode builds a fresh ``Scheduler`` and autotunes from an even split
until the slowest machine takes at most ``eps`` longer than the fastest.
Episode ``e`` runs cluster ``order[e % len(clusters)]``; the benchmark seed
draws ``order``, so every seed tunes the same clusters, in another order.
Set-up tunes each cluster once, which compiles every bank shape that
autotune reaches.
"""

from __future__ import annotations

from bench.flat import FlatJob
from bench.harness import Window, now


class Loop(FlatJob):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.order = self.rng.permutation(len(self.clusters))

    def episode(self, e: int) -> None:
        sess = self.construct()
        self.autotune(sess, int(self.order[e % len(self.order)]))
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
