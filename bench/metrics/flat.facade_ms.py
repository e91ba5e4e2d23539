"""Mean host time per flat round outside the partition: the round's latency
less the ``speedstore.partition`` spans inside it (the EMA loop, the host
``add_point`` loop and the fold dispatch)."""

import bisect


def read(run):
    parts = sorted(run.spans.get("speedstore.partition", []))
    if not run.rounds:
        return None
    starts = [a for a, _ in parts]
    total = 0.0
    for r in run.rounds:
        inside = 0.0
        for a, b in parts[bisect.bisect_left(starts, r.t0):]:
            if a >= r.t1:
                break
            inside += min(b, r.t1) - a
        total += (r.t1 - r.t0) - inside
    return 1e3 * total / len(run.rounds)
