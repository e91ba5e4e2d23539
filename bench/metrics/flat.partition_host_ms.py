"""Mean host time of ``speedstore.partition`` outside the wait for the
partition program (its ``modelbank.partition.wait`` children): the
monotone check, validation, uploads and int conversion, per repartition."""

import bisect


def read(run):
    parts = run.spans.get("speedstore.partition", [])
    waits = run.spans.get("modelbank.partition.wait", [])
    if not parts or not waits:
        return None
    waits = sorted(waits)
    starts = [a for a, _ in waits]
    total = 0.0
    for p0, p1 in parts:
        inside = 0.0
        for a, b in waits[bisect.bisect_left(starts, p0):]:
            if a >= p1:
                break
            if b <= p1:
                inside += b - a
        total += (p1 - p0) - inside
    return 1e3 * total / len(parts)
