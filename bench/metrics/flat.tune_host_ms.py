"""Mean host time per measured round of ``Scheduler.autotune`` outside the
executor's run and the fold and partition layers: the ``scheduler.autotune``
spans less their ``scheduler.measure``, ``speedstore.fold_in`` and
``speedstore.partition`` children, over the ``scheduler.measure`` spans
inside them."""

import bisect

CHILDREN = ("scheduler.measure", "speedstore.fold_in", "speedstore.partition")


def read(run):
    tunes = run.spans.get("scheduler.autotune", [])
    kids = sorted((a, b, name) for name in CHILDREN for a, b in run.spans.get(name, []))
    if not tunes or not kids:
        return None
    starts = [a for a, _, _ in kids]
    total, rounds = 0.0, 0
    for t0, t1 in tunes:
        inside = 0.0
        for a, b, name in kids[bisect.bisect_left(starts, t0):]:
            if a >= t1:
                break
            if b <= t1:
                inside += b - a
                rounds += name == "scheduler.measure"
        total += (t1 - t0) - inside
    if not rounds:
        return None
    return 1e3 * total / rounds
